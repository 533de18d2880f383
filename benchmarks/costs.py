"""Operations and bytes that the algorithms need, from their shapes alone.

The yardstick's own arithmetic: a roofline share divides these by a measured
device time and a peak from ``peaks.json``. Nothing here reads the program.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))

#: bf16 MXU passes that one float32 product costs at each jax precision:
#: "default" rounds both operands to bf16 once; "high" is XLA's bf16_3x
#: (hi*hi + hi*lo + lo*hi); "highest" is bf16_6x.
PASSES = {"default": 1, "high": 3, "highest": 6}


def load_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in benchmarks/peaks.json")
    return table[device_kind]


def matmul_flops(m: int, k: int, n: int) -> float:
    """Useful operations of C = A x B: one multiply and one add per term."""
    return 2.0 * m * k * n


def matmul_bytes(m: int, k: int, n: int, itemsize: int) -> float:
    """Least bytes moved: each operand read once, the product written once."""
    return float(itemsize) * (m * k + k * n + m * n)


def matmul_least_seconds(m: int, k: int, n: int, itemsize: int,
                         precision: str, chips: int, peaks: dict) -> dict:
    """The least time ``chips`` chips could take for the product, and which
    peak bounds it. ``passes`` bf16 products stand behind one f32 product."""
    passes = PASSES[precision]
    compute_s = passes * matmul_flops(m, k, n) / chips / peaks["bf16_flops_per_s"]
    memory_s = matmul_bytes(m, k, n, itemsize) / chips / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s),
            "bound": "compute" if compute_s >= memory_s else "memory",
            "compute_s": compute_s, "memory_s": memory_s, "passes": passes}


def paged_attention_cost(batch: int, table_width: int, page_len: int,
                         kv_heads: int, group: int, dh: int,
                         itemsize: int) -> dict:
    """One paged decode-attention call (copied from
    ``marlin_tpu/ops/paged_attention.py:paged_attention_cost``): two
    (group*dh x page_len) contractions per (row, page, kv head); one pass
    over each row's table extent of the K and V slabs plus q and the output."""
    t = batch * table_width * kv_heads
    flops = 2.0 * 2.0 * t * group * dh * page_len
    kv_bytes = 2.0 * t * page_len * dh * itemsize
    qo_bytes = 2.0 * batch * kv_heads * group * dh * itemsize
    return {"flops": flops, "bytes": kv_bytes + qo_bytes}


def decoder_param_count(d_model: int, n_layers: int, expansion_ratio: int,
                        vocab_size: int) -> dict:
    """Parameters of the two-matrix-FFN decoder with a tied head."""
    per_layer = 4 * d_model * d_model + 2 * expansion_ratio * d_model * d_model
    return {"per_layer": per_layer, "layers": n_layers * per_layer,
            "embedding": vocab_size * d_model,
            "total": n_layers * per_layer + vocab_size * d_model}


def kv_bytes_per_token(d_model: int, n_layers: int, itemsize: int) -> int:
    return 2 * n_layers * d_model * itemsize
