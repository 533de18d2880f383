"""Operations and bytes of the ``serve_laguna`` cells' kernels, from the
configuration file's shapes alone (beside ``costs.py``, which holds the
dense cells'). Nothing here reads the program."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def expert_bytes(cfg: dict) -> float:
    """One routed expert's three matrices (gate, up, down), read once."""
    return (3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * _ITEMSIZE[cfg["param_dtype"]])


def expert_flops(cfg: dict) -> float:
    """One assignment through one expert: three matmuls, a multiply and an
    add per term."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def moe_least_seconds(touched: float, local: float, cfg: dict,
                      peaks: dict) -> dict:
    """The least seconds for the grouped expert matmuls of dispatches that
    touched ``touched`` held experts (summed over layers and dispatches)
    with ``local`` assignments: every touched expert's weights read once
    over the memory peak, against the assignments' flops over the bf16 peak,
    the larger."""
    memory_s = touched * expert_bytes(cfg) / peaks["hbm_bytes_per_s"]
    compute_s = local * expert_flops(cfg) / peaks["bf16_flops_per_s"]
    return {"seconds": max(memory_s, compute_s),
            "bound": "memory" if memory_s >= compute_s else "compute",
            "memory_s": memory_s, "compute_s": compute_s}


def layers_of(cfg: dict, kind: str) -> int:
    """How many of the held layers are ``full_attention`` /
    ``sliding_attention``."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(kind)


def kv_page_bytes(cfg: dict) -> float:
    """One page of one layer, K and V."""
    return (2.0 * cfg["engine"]["page_len"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * _ITEMSIZE[cfg["compute_dtype"]])


def attention_least_seconds(pages: float, kind: str, cfg: dict,
                            peaks: dict) -> float:
    """The least seconds for decode attention that must read ``pages`` KV
    pages in each layer of ``kind``: memory-bound (a query meets a key
    once)."""
    return (pages * layers_of(cfg, kind) * kv_page_bytes(cfg)
            / peaks["hbm_bytes_per_s"])
