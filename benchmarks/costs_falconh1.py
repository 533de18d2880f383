"""Operations and bytes of the ``serve_falconh1`` cells' state-space
kernels, from the configuration file's shapes alone (beside ``costs.py``,
``costs_laguna.py`` and ``costs_mistral4.py``; the attention kernel is priced
by ``costs_laguna.py``, whose keys the configuration file carries). Nothing
here reads the program."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def state_bytes(cfg: dict) -> float:
    """One row's recurrent state in one layer: heads x head x state values
    in the state's dtype."""
    return (float(cfg["mamba_n_heads"]) * cfg["mamba_d_head"]
            * cfg["mamba_d_state"]
            * _ITEMSIZE[cfg.get("ssm_state_dtype", "float32")])


def conv_dim(cfg: dict) -> int:
    """Channels under the convolution: ``[x | B | C]``."""
    return (cfg["mamba_d_ssm"]
            + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"])


def tail_bytes(cfg: dict) -> float:
    """One row's convolution tail in one layer: the last ``d_conv - 1``
    inputs, in the compute dtype."""
    return (float(cfg["mamba_d_conv"] - 1) * conv_dim(cfg)
            * _ITEMSIZE[cfg["compute_dtype"]])


def slot_bytes(cfg: dict) -> float:
    """One row's state slot over the held layers (what admission charges)."""
    return cfg["num_hidden_layers"] * (state_bytes(cfg) + tail_bytes(cfg))


def ssm_decode_least_seconds(state_rows: float, cfg: dict,
                             peaks: dict) -> float:
    """The least seconds for the decode state update over calls that carry
    ``state_rows`` live rows in all (summed over calls), in each held layer:
    every live row's state and tail read once and written once, over the
    memory peak. Memory-bound: a state value meets five operations."""
    return (state_rows * cfg["num_hidden_layers"]
            * 2.0 * (state_bytes(cfg) + tail_bytes(cfg))
            / peaks["hbm_bytes_per_s"])


def scan_token_flops(cfg: dict) -> float:
    """One token of the chunked scan in one layer, a multiply and an add a
    term: inside its block of ``Q = mamba_chunk_size`` tokens the scores
    ``C B^T`` (Q/2 visible pairs on average, counted as the whole block's Q:
    the program computes the square) over ``state`` columns a group, the
    masked product with ``x`` over ``Q`` tokens a head and channel; what the
    token leaves to the block's state and what it reads of the entering
    state, ``state`` columns a head and channel each."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    heads, p, g = cfg["mamba_n_heads"], cfg["mamba_d_head"], \
        cfg["mamba_n_groups"]
    return 2.0 * (g * q * n + heads * p * q + 2 * heads * p * n)


def scan_token_bytes(cfg: dict) -> float:
    """One token of the chunked scan in one layer, the least that moves:
    its ``x``, ``B``, ``C`` read in the compute dtype and its ``y`` written
    in float32. (The block states need not leave the chip between the blocks
    of one chunk: :func:`ssm_prefill_least_seconds` counts the state once a
    chunk.)"""
    return (conv_dim(cfg) * _ITEMSIZE[cfg["compute_dtype"]]
            + 4.0 * cfg["mamba_n_heads"] * cfg["mamba_d_head"])


def ssm_prefill_least_seconds(tokens: float, chunks: float, cfg: dict,
                              peaks: dict) -> dict:
    """The least seconds for the chunked scan over ``chunks`` prefill chunks
    that hold ``tokens`` valid tokens in all, in each held layer: the
    tokens' flops over the bf16 peak against the bytes over the memory peak
    (the tokens' own, and the row's state read once and written once a
    chunk), the larger."""
    layers = cfg["num_hidden_layers"]
    compute_s = tokens * layers * scan_token_flops(cfg) \
        / peaks["bf16_flops_per_s"]
    memory_s = layers * (tokens * scan_token_bytes(cfg)
                         + chunks * 2.0 * state_bytes(cfg)) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(memory_s, compute_s),
            "bound": "memory" if memory_s >= compute_s else "compute",
            "memory_s": memory_s, "compute_s": compute_s}
