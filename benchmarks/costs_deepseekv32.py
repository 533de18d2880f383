"""Operations and bytes of the ``deepseek_v32`` family's token selection, from
the configuration's sizes and the rows' lengths alone: the work of the MODEL
(pairs a query scores, entries it attends, their bytes), never of an
implementation, so that a later kernel is read by the same yardstick.

A layer's indexer scores, for a query at position ``p``, its ``p + 1`` keys
(``index_n_heads`` heads of ``index_head_dim``: ``2 J D`` operations a pair,
16,384 at the published sizes; an index key is ``D`` values in the compute
dtype, 256 B), and the query then attends ``min(index_topk, p + 1)`` cache
entries of ``entry_width`` stored values (1280 B), every head, absorbed: ``2
(entry_dim + kv_lora_rank)`` operations a head and entry.
"""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def layers(cfg: dict) -> int:
    """Every held layer has an indexer."""
    return int(cfg["num_hidden_layers"])


def entry_width(cfg: dict) -> int:
    """Columns of a cache entry as stored: whole lane tiles of 128."""
    dim = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    return -(-dim // 128) * 128


def entry_bytes(cfg: dict) -> float:
    return float(entry_width(cfg)) * _ITEMSIZE[cfg["compute_dtype"]]


def index_key_bytes(cfg: dict) -> float:
    return float(cfg["index_head_dim"]) * _ITEMSIZE[cfg["compute_dtype"]]


def pair_flops(cfg: dict) -> float:
    """One (query, key) pair of the index score: every head's dot product."""
    return 2.0 * cfg["index_n_heads"] * cfg["index_head_dim"]


def entry_flops(cfg: dict) -> float:
    """One (query, selected entry) pair of the absorbed attention: every
    head's score over the entry and its value over the latent."""
    return 2.0 * cfg["num_attention_heads"] * (
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"] + cfg["kv_lora_rank"])


def selects(start: int, cfg: dict) -> bool:
    """Whether a prefill chunk that begins at ``start`` selects: one that
    lies wholly below ``index_topk`` attends every position it can see."""
    return start + int(cfg["engine"]["prefill_chunk"]) > int(cfg["index_topk"])


def causal_pairs(start: int, tokens: int) -> float:
    """Pairs that ``tokens`` queries at ``start``.. score, a layer."""
    return float(tokens) * start + tokens * (tokens + 1) / 2.0


def selected_entries(start: int, tokens: int, cfg: dict) -> float:
    """Entries that ``tokens`` queries at ``start``.. attend, a layer."""
    k = int(cfg["index_topk"])
    return float(sum(min(k, start + i + 1) for i in range(tokens)))


def _larger(flops: float, nbytes: float, peaks: dict) -> dict:
    compute_s = flops / peaks["bf16_flops_per_s"]
    memory_s = nbytes / peaks["hbm_bytes_per_s"]
    return {"seconds": max(compute_s, memory_s),
            "bound": "compute" if compute_s >= memory_s else "memory",
            "compute_s": compute_s, "memory_s": memory_s}


def index_prefill_least_seconds(pairs: float, queries: float, keys: float,
                                cfg: dict, peaks: dict) -> dict:
    """The least seconds for the index scores of prefill chunks that scored
    ``pairs`` pairs over ALL layers (``dsa_pairs_scored``) with ``queries``
    queries against ``keys`` key positions in all (a layer): the pairs'
    operations over the bf16 peak, or the bytes (every query's heads, every
    chunk's keys once) over the memory peak, the larger."""
    item = _ITEMSIZE[cfg["compute_dtype"]]
    nbytes = layers(cfg) * (
        queries * cfg["index_n_heads"] * cfg["index_head_dim"] * item
        + keys * index_key_bytes(cfg))
    return _larger(pairs * pair_flops(cfg), nbytes, peaks)


def index_decode_least_seconds(tokens_held: float, cfg: dict,
                               peaks: dict) -> dict:
    """The least seconds for the index scores of decode calls whose live rows
    hold ``tokens_held`` tokens, summed over rows AND layers
    (``dsa_tokens_held``): each row reads its own index keys once."""
    return _larger(tokens_held * pair_flops(cfg),
                   tokens_held * index_key_bytes(cfg), peaks)


def attend_least_seconds(entries: float, cfg: dict, peaks: dict) -> dict:
    """The least seconds for the attention over ``entries`` selected
    entries, summed over rows or queries AND layers: each entry read once
    (``entry_width`` stored values) against the absorbed form's operations,
    the larger."""
    return _larger(entries * entry_flops(cfg), entries * entry_bytes(cfg),
                   peaks)
