"""Operations and bytes of the ``serve_minicpmsala`` cells' kernels, from the
configuration file's shapes alone (beside ``costs.py`` and the other
configurations' ``costs_*.py``). Nothing here reads the program."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def layers(cfg: dict, kind: str) -> int:
    """How many of the held layers are of ``kind`` (``minicpm4`` /
    ``lightning-attn``)."""
    return cfg["mixer_types"][:cfg["num_hidden_layers"]].count(kind)


# ------------------------------------------------------------ sparse attention


def block_bytes(cfg: dict) -> float:
    """One block of ONE KV head as the decode kernel reads it: ``block_size``
    tokens x ``head_dim`` lanes, keys and values, in the compute dtype."""
    return (2.0 * cfg["sparse_config"]["block_size"] * cfg["head_dim"]
            * _ITEMSIZE[cfg["compute_dtype"]])


def sparse_decode_least_seconds(blocks_attended: float, rows: float,
                                cfg: dict, peaks: dict) -> float:
    """The least seconds for the block walk over calls whose live rows
    attended ``blocks_attended`` blocks in all (the engine's counter: summed
    over rows, KV heads and sparse layers) and held ``rows`` live rows
    (summed over calls): each attended block's K and V read once, each row's
    queries read and outputs written once a sparse layer, over the memory
    peak. Memory-bound: a key meets a group's 16 queries."""
    io = (2.0 * rows * layers(cfg, "minicpm4") * cfg["num_attention_heads"]
          * cfg["head_dim"] * _ITEMSIZE[cfg["compute_dtype"]])
    return (blocks_attended * block_bytes(cfg) + io) / peaks["hbm_bytes_per_s"]


def selected_pairs(start: int, tokens: int, cfg: dict) -> float:
    """The (query, key) pairs the SELECTION leaves a chunk of ``tokens``
    valid queries from position ``start``: a query at ``t`` below
    ``dense_len`` meets ``t + 1`` keys; from it on, the whole of ``topk - 1``
    blocks and its own block up to itself (every block, where fewer than
    ``topk`` exist)."""
    sc = cfg["sparse_config"]
    B, total = sc["block_size"], 0.0
    for t in range(start, start + tokens):
        if t < sc["dense_len"] or t // B + 1 <= sc["topk"]:
            total += t + 1
        else:
            total += (sc["topk"] - 1) * B + t % B + 1
    return total


def sparse_prefill_least_seconds(pairs: float, cfg: dict,
                                 peaks: dict) -> float:
    """The least seconds for prefill's sparse attention over ``pairs``
    selected (query, key) pairs a query head (:func:`selected_pairs`, summed
    over chunks), in each sparse layer: a multiply and an add a pair and
    lane for the score and again for the value, every query head, over the
    bf16 peak. The same work whatever computes it: a masked-dense kernel
    spends the dense FLOPs and reads low; one that skips unselected blocks
    can approach 100 and never pass it."""
    flops = (pairs * layers(cfg, "minicpm4") * cfg["num_attention_heads"]
             * 2 * 2.0 * cfg["head_dim"])
    return flops / peaks["bf16_flops_per_s"]


# ------------------------------------------------------------------ lightning


def state_bytes(cfg: dict) -> float:
    """One row's recurrent state in one lightning layer as stored: heads x
    head_dim x head_dim values in the state's dtype (whole tiles)."""
    return (float(cfg["lightning_nh"]) * cfg["lightning_head_dim"] ** 2
            * _ITEMSIZE[cfg.get("lightning_state_dtype", "float32")])


def slot_bytes(cfg: dict) -> float:
    """One row's state slot (or one snapshot) over the held layers."""
    return layers(cfg, "lightning-attn") * state_bytes(cfg)


def lightning_decode_least_seconds(state_rows: float, cfg: dict,
                                   peaks: dict) -> float:
    """The least seconds for the decode state update over calls that carry
    ``state_rows`` live rows in all, in each lightning layer: every live
    row's state read once and written once, over the memory peak.
    Memory-bound: a state value meets four operations."""
    return (state_rows * layers(cfg, "lightning-attn") * 2.0
            * state_bytes(cfg) / peaks["hbm_bytes_per_s"])


def scan_token_flops(cfg: dict) -> float:
    """One token of the block form in one lightning layer, a multiply and an
    add a term, at the block ``C = lightning_chunk_size``: a head's ``C x C``
    scores ``Q K^T`` (the program computes the square: C keys a token) and
    their product with ``V``, the read ``Q S`` of the entering state and what
    the token leaves to it, ``K^T V`` (``head_dim x head_dim`` each)."""
    c, d = cfg.get("lightning_chunk_size", 64), cfg["lightning_head_dim"]
    return 2.0 * cfg["lightning_nh"] * (2 * c * d + 2 * d * d)


def scan_token_bytes(cfg: dict) -> float:
    """One token of the block form in one layer, the least that moves: its
    ``q``, ``k``, ``v`` read in the compute dtype and its output written in
    float32."""
    hd = cfg["lightning_nh"] * cfg["lightning_head_dim"]
    return 3.0 * hd * _ITEMSIZE[cfg["compute_dtype"]] + 4.0 * hd


def lightning_prefill_least_seconds(tokens: float, chunks: float, cfg: dict,
                                    peaks: dict) -> dict:
    """The least seconds for the block form over ``chunks`` prefill chunks
    that hold ``tokens`` valid tokens in all, in each lightning layer: the
    tokens' flops over the bf16 peak against the bytes over the memory peak
    (the tokens' own, and the row's state read once and written once a
    chunk), the larger."""
    n = layers(cfg, "lightning-attn")
    compute_s = tokens * n * scan_token_flops(cfg) / peaks["bf16_flops_per_s"]
    memory_s = n * (tokens * scan_token_bytes(cfg)
                    + chunks * 2.0 * state_bytes(cfg)) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(memory_s, compute_s),
            "bound": "memory" if memory_s >= compute_s else "compute",
            "memory_s": memory_s, "compute_s": compute_s}
