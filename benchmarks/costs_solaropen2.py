"""Operations and bytes of the ``serve_solaropen2`` cells' KDA kernels (a
delta rule whose decay is a vector a head), from the configuration file's
shapes alone (beside ``costs.py`` and the other ``costs_*.py``; the
attention kernel of the GQA layers and the expert layer are priced by
``costs_laguna.py``, whose keys the configuration file carries). Nothing
here reads the program."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
#: tokens whose pairs the chunked form meets exactly, inside one block
_SUB_BLOCK = 16


def kda_layers(cfg: dict) -> int:
    """How many of the held layers are KDA layers: those ``gqa_layers`` does
    not name."""
    gqa = set(cfg["gqa_layers"])
    return sum(i not in gqa for i in range(cfg["num_hidden_layers"]))


def _heads_dim(cfg: dict) -> tuple:
    la = cfg["linear_attn_config"]
    return la["num_heads"], la["head_dim"]


def state_bytes(cfg: dict) -> float:
    """One row's recurrent state in one layer AS STORED: heads x key x
    value values in the state's dtype, dense (the slab is ``(slots, 128,
    64 x 128)``: whole lane tiles, nothing is padded)."""
    h, d = _heads_dim(cfg)
    return float(h) * d * d * _ITEMSIZE[cfg.get("kda_state_dtype", "float32")]


def conv_dim(cfg: dict) -> int:
    """Channels under the convolution: ``[q | k | v]``."""
    h, d = _heads_dim(cfg)
    return 3 * h * d


def tail_bytes(cfg: dict) -> float:
    """One row's convolution tail in one layer: the last ``taps - 1``
    inputs, in the compute dtype."""
    taps = cfg["linear_attn_config"]["short_conv_kernel_size"]
    return float(taps - 1) * conv_dim(cfg) * _ITEMSIZE[cfg["compute_dtype"]]


def slot_bytes(cfg: dict) -> float:
    """One row's state slot (or one snapshot) over the held layers."""
    return kda_layers(cfg) * (state_bytes(cfg) + tail_bytes(cfg))


def kda_decode_least_seconds(state_rows: float, cfg: dict,
                             peaks: dict) -> float:
    """The least seconds for the decode state update over calls that carry
    ``state_rows`` live rows in all (summed over calls), in each KDA layer:
    every live row's state and tail read once and written once, over the
    memory peak. Memory-bound: a state value meets seven operations."""
    return (state_rows * kda_layers(cfg)
            * 2.0 * (state_bytes(cfg) + tail_bytes(cfg))
            / peaks["hbm_bytes_per_s"])


def scan_token_flops(cfg: dict) -> float:
    """One token of the chunked (WY) form in one layer, a multiply and an
    add a term, at the block ``C = kda_chunk_size`` the program uses. A
    head: the two score matrices ``K K^T`` and ``Q K^T`` with the decay
    inside the contraction, over the earlier tokens of the block the form
    meets (whole sub-blocks of 16 before the token's own, and its own: ``(C
    + 16) / 2`` keys on average, ``key`` channels each); the triangular
    system against ``[V | K]`` (C / 2 earlier tokens on average, ``value +
    key`` columns); the steps' correction ``U S`` and the read ``Q S``
    (``key x value`` each), the in-block output ``P D`` (C x value) and what
    the token leaves to the state ``K^T D`` (``key x value``)."""
    c = cfg.get("kda_chunk_size", 64)
    h, d = _heads_dim(cfg)
    return 2.0 * h * (2 * ((c + _SUB_BLOCK) / 2) * d + (c / 2) * (d + d)
                      + 3 * d * d + c * d)


def scan_token_bytes(cfg: dict) -> float:
    """One token of the chunked form in one layer, the least that moves: its
    ``q``, ``k``, ``v`` read in the compute dtype, its log-decays (one a
    channel of the key, float32) read and its output written in float32.
    (The block states need not leave the chip between the blocks of one
    chunk: :func:`kda_prefill_least_seconds` counts the state once a
    chunk.)"""
    h, d = _heads_dim(cfg)
    return conv_dim(cfg) * _ITEMSIZE[cfg["compute_dtype"]] + 2 * 4.0 * h * d


def kda_prefill_least_seconds(tokens: float, chunks: float, cfg: dict,
                              peaks: dict) -> dict:
    """The least seconds for the chunked form over ``chunks`` prefill chunks
    that hold ``tokens`` valid tokens in all, in each KDA layer: the tokens'
    flops over the bf16 peak against the bytes over the memory peak (the
    tokens' own, and the row's state read once and written once a chunk),
    the larger."""
    layers = kda_layers(cfg)
    compute_s = tokens * layers * scan_token_flops(cfg) \
        / peaks["bf16_flops_per_s"]
    memory_s = layers * (tokens * scan_token_bytes(cfg)
                         + chunks * 2.0 * state_bytes(cfg)) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(memory_s, compute_s),
            "bound": "memory" if memory_s >= compute_s else "compute",
            "memory_s": memory_s, "compute_s": compute_s}
