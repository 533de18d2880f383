"""Operations and bytes of the ``serve_mistral4`` cells' latent decode
kernel and of prefill's flash kernel, from the configuration file's shapes
alone (beside ``costs.py`` and ``costs_laguna.py``; the expert layers are
priced by ``costs_laguna.py``, whose keys the configuration file carries).
Nothing here reads the program."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
_LANES = 128


def entry_dim(cfg: dict) -> int:
    """Values a token leaves in a layer's cache: the latent and the shared
    rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def entry_width(cfg: dict) -> int:
    """Columns the slab stores an entry: whole lane tiles (the
    configuration file's ``assumed.cache_entry_padded``)."""
    return -(-entry_dim(cfg) // _LANES) * _LANES


def latent_page_bytes(cfg: dict) -> float:
    """One page of one layer as the kernel reads it, ONCE, padding
    included: it is the key and, in its first ``kv_lora_rank`` columns, the
    value."""
    return (float(cfg["engine"]["page_len"]) * entry_width(cfg)
            * _ITEMSIZE[cfg["compute_dtype"]])


def latent_position_flops(cfg: dict) -> float:
    """One (row, position) of absorbed decode attention in one layer: every
    head's score over the entry's ``kv_rank + rope`` columns and its value
    sum over the ``kv_rank``, a multiply and an add each."""
    return (2.0 * cfg["num_attention_heads"]
            * (entry_dim(cfg) + cfg["kv_lora_rank"]))


def mla_decode_least_seconds(pages: float, positions: float, cfg: dict,
                             peaks: dict) -> dict:
    """The least seconds for the latent decode kernel over decode calls
    whose live rows hold their attended positions in ``pages`` pages
    (``positions`` positions; both summed over rows and calls), in each
    held layer: every such page read once over the memory peak, against the
    positions' flops over the bf16 peak, the larger."""
    layers = cfg["num_hidden_layers"]
    memory_s = pages * layers * latent_page_bytes(cfg) \
        / peaks["hbm_bytes_per_s"]
    compute_s = positions * layers * latent_position_flops(cfg) \
        / peaks["bf16_flops_per_s"]
    return {"seconds": max(memory_s, compute_s),
            "bound": "memory" if memory_s >= compute_s else "compute",
            "memory_s": memory_s, "compute_s": compute_s}


def prefill_pair_flops(cfg: dict) -> float:
    """One (query, visible key) pair of unabsorbed prefill attention in one
    layer: every head's score over ``qk_nope + qk_rope`` columns and its
    value sum over ``v_head_dim``, a multiply and an add each."""
    return (2.0 * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
               + cfg["v_head_dim"]))


def chunk_pairs(start: int, width: int) -> float:
    """(query, visible key) pairs of one prefill chunk of ``width`` queries
    whose first stands at position ``start``: query ``i`` sees ``start + i +
    1`` keys. The program computes the chunk's whole width, padding
    included."""
    return float(width) * start + width * (width + 1) / 2.0


def mla_prefill_least_seconds(pairs: float, cfg: dict, peaks: dict) -> float:
    """The least seconds for prefill's attention kernel over chunks with
    ``pairs`` (query, visible key) pairs in all, in each held layer:
    compute-bound (a chunk of 1024 queries meets each key it reads 1024
    times)."""
    return (pairs * cfg["num_hidden_layers"] * prefill_pair_flops(cfg)
            / peaks["bf16_flops_per_s"])
