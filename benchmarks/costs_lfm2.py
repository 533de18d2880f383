"""Operations and bytes of the ``serve_lfm2`` cells' gated short
convolution, from the configuration file's shapes alone (beside ``costs.py``
and the other four ``costs_*.py``; the attention kernel of the full layers
and the expert layer are priced by ``costs_laguna.py``, whose keys the
configuration file carries). They price the mixer's WORK, whatever
implements it. Nothing here reads the program."""

from __future__ import annotations

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def conv_layers(cfg: dict) -> int:
    """How many of the held layers are ``conv``."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count("conv")


def mixer_params(cfg: dict) -> int:
    """One mixer's projections: ``W_in`` (d, 3d) and ``W_out`` (d, d)."""
    d = cfg["hidden_size"]
    return d * 3 * d + d * d


def weight_bytes(cfg: dict) -> float:
    """One mixer's projections as held, read once a call."""
    return float(mixer_params(cfg)) * _ITEMSIZE[cfg["param_dtype"]]


def tail_bytes(cfg: dict) -> float:
    """One row's tail in one layer: the last ``conv_L_cache - 1`` gated
    inputs, in the compute dtype. All a row keeps of its past there."""
    return (float(cfg["conv_L_cache"] - 1) * cfg["hidden_size"]
            * _ITEMSIZE[cfg["compute_dtype"]])


def slot_bytes(cfg: dict) -> float:
    """One row's state slot (or one snapshot) over the held layers."""
    return conv_layers(cfg) * tail_bytes(cfg)


def conv_decode_least_seconds(calls: float, state_rows: float, cfg: dict,
                              peaks: dict) -> float:
    """The least seconds for the mixers of ``calls`` decode calls that carry
    ``state_rows`` live rows in all, in each conv layer: the projections
    read once a call and every live row's tail read once and written once,
    over the memory peak. Memory-bound: at 96 rows a weight meets 192
    operations a byte-pair, under the chip's 240."""
    return conv_layers(cfg) * (
        calls * weight_bytes(cfg) + state_rows * 2.0 * tail_bytes(cfg)) \
        / peaks["hbm_bytes_per_s"]


def token_flops(cfg: dict) -> float:
    """One token through one mixer, a multiply and an add a term: the two
    projections, the taps, and the two gates' products."""
    d = cfg["hidden_size"]
    return 2.0 * mixer_params(cfg) + 2.0 * cfg["conv_L_cache"] * d + 2.0 * d


def conv_prefill_least_seconds(tokens: float, cfg: dict,
                               peaks: dict) -> float:
    """The least seconds for the mixers of prefill chunks that hold
    ``tokens`` valid tokens in all, in each conv layer: the tokens' flops
    over the bf16 peak. Compute-bound: a chunk of some hundred tokens meets
    each weight that often."""
    return tokens * conv_layers(cfg) * token_flops(cfg) \
        / peaks["bf16_flops_per_s"]
