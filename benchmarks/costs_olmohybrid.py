"""Operations and bytes of the ``serve_olmohybrid`` cells' delta-rule
kernels, from the configuration file's shapes alone (beside ``costs.py``,
``costs_laguna.py``, ``costs_mistral4.py`` and ``costs_falconh1.py``; the
attention kernel of the full layers is priced by ``costs_laguna.py``, whose
keys the configuration file carries). Nothing here reads the program."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def linear_layers(cfg: dict) -> int:
    """How many of the held layers are ``linear_attention``."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "linear_attention")


def state_bytes(cfg: dict) -> float:
    """One row's recurrent state in one layer AS STORED: heads x key x
    value values in the state's dtype, dense (the slab is ``(slots, key,
    heads * value)``: 5760 lanes are 45 whole tiles, nothing is padded)."""
    return (float(cfg["linear_num_key_heads"]) * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"]
            * _ITEMSIZE[cfg.get("linear_state_dtype", "float32")])


def conv_dim(cfg: dict) -> int:
    """Channels under the convolution: ``[q | k | v]``."""
    return cfg["linear_num_key_heads"] * (
        2 * cfg["linear_key_head_dim"] + cfg["linear_value_head_dim"])


def tail_bytes(cfg: dict) -> float:
    """One row's convolution tail in one layer: the last ``taps - 1``
    inputs, in the compute dtype."""
    return (float(cfg["linear_conv_kernel_dim"] - 1) * conv_dim(cfg)
            * _ITEMSIZE[cfg["compute_dtype"]])


def slot_bytes(cfg: dict) -> float:
    """One row's state slot (or one snapshot) over the held layers."""
    return linear_layers(cfg) * (state_bytes(cfg) + tail_bytes(cfg))


def gdn_decode_least_seconds(state_rows: float, cfg: dict,
                             peaks: dict) -> float:
    """The least seconds for the decode state update over calls that carry
    ``state_rows`` live rows in all (summed over calls), in each linear
    layer: every live row's state and tail read once and written once, over
    the memory peak. Memory-bound: a state value meets seven operations."""
    return (state_rows * linear_layers(cfg)
            * 2.0 * (state_bytes(cfg) + tail_bytes(cfg))
            / peaks["hbm_bytes_per_s"])


def scan_token_flops(cfg: dict) -> float:
    """One token of the chunked (WY) form in one layer, a multiply and an
    add a term, at the block ``C = linear_chunk_size`` the program uses. A
    head: the two ``C x C`` score matrices ``K K^T`` and ``Q K^T`` (the
    program computes the squares: C key values each a token); the triangular
    system against ``[V | K]`` (C / 2 earlier tokens on average, ``value +
    key`` columns); the steps' correction ``U S`` and the read ``Q S``
    (``key x value`` each), the in-block output ``P D`` (C x value) and what
    the token leaves to the state ``K^T D`` (``key x value``)."""
    c = cfg.get("linear_chunk_size", 64)
    k, v = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 2.0 * cfg["linear_num_key_heads"] * (
        2 * c * k + (c / 2) * (v + k) + 3 * k * v + c * v)


def scan_token_bytes(cfg: dict) -> float:
    """One token of the chunked form in one layer, the least that moves: its
    ``q``, ``k``, ``v`` read in the compute dtype and its output written in
    float32. (The block states need not leave the chip between the blocks of
    one chunk: :func:`gdn_prefill_least_seconds` counts the state once a
    chunk.)"""
    return (conv_dim(cfg) * _ITEMSIZE[cfg["compute_dtype"]]
            + 4.0 * cfg["linear_num_key_heads"]
            * cfg["linear_value_head_dim"])


def gdn_prefill_least_seconds(tokens: float, chunks: float, cfg: dict,
                              peaks: dict) -> dict:
    """The least seconds for the chunked form over ``chunks`` prefill chunks
    that hold ``tokens`` valid tokens in all, in each linear layer: the
    tokens' flops over the bf16 peak against the bytes over the memory peak
    (the tokens' own, and the row's state read once and written once a
    chunk), the larger."""
    layers = linear_layers(cfg)
    compute_s = tokens * layers * scan_token_flops(cfg) \
        / peaks["bf16_flops_per_s"]
    memory_s = layers * (tokens * scan_token_bytes(cfg)
                         + chunks * 2.0 * state_bytes(cfg)) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(memory_s, compute_s),
            "bound": "memory" if memory_s >= compute_s else "compute",
            "memory_s": memory_s, "compute_s": compute_s}
