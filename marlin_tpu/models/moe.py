"""Mixture-of-experts FFN with expert parallelism, GShard-style on TPU.

No reference analog (the reference's only DNN is the 2-layer MLP,
examples/NeuralNetwork.scala) — this exists because expert parallelism is the
remaining canonical scaling family next to data/tensor/sequence/pipeline
parallelism, and the brief's multi-chip mandate names it explicitly. The
design is the classic dense-dispatch MoE of the TPU lineage (GShard / Switch):
static-shape capacity-based routing expressed as einsums, experts laid out
over a mesh axis, the token shuffle appearing as XLA-inserted all_to_all
collectives from sharding constraints — never hand-written sends.

Memory design (the long-context constraint this package lives under): the
dispatch one-hot is O(tokens x experts x capacity) = O(S² · k · cf / E) if
built for the whole sequence — quadratic in S, exactly the failure mode the
flash kernels exist to avoid. Routing is therefore *grouped* (`group_size`
tokens at a time, the GShard grouping): a ``lax.scan`` over groups keeps ONE
group's dispatch tensor live (O(g·E·c_g), independent of S), while each
group's expert matmuls still run all experts batched on the MXU. Gating and
the load-balance statistics are computed per group in f32.

Capacity semantics: each expert accepts at most ``c_g = ceil(g·k·cf/E)``
tokens per group; overflow tokens lose that expert choice (their kept
choices renormalize — standard Switch behavior, exact at cf large enough).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..mesh import ROWS

__all__ = ["init_moe", "moe_ffn", "moe_decode_ffn", "moe_experts_ffn",
           "moe_capacity", "shard_moe_params"]


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32) -> dict:
    """Router + per-expert FFN params. ``wg``: (d, E) gating; ``w1``:
    (E, d, ff); ``w2``: (E, ff, d). The leading expert axis is the one a
    trainer shards over the mesh (see :func:`moe_ffn`'s ``axis``)."""
    if n_experts < 2:
        raise ValueError(f"n_experts must be >= 2, got {n_experts}")
    k0, k1, k2 = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d_model)
    return {
        "wg": jax.random.normal(k0, (d_model, n_experts), dtype) * s,
        "w1": jax.random.normal(k1, (n_experts, d_model, d_ff), dtype) * s,
        "w2": jax.random.normal(k2, (n_experts, d_ff, d_model), dtype)
        / math.sqrt(d_ff),
    }


def moe_capacity(group: int, n_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Per-expert slot count for one routing group (static)."""
    return max(1, math.ceil(group * top_k * capacity_factor / n_experts))


def shard_moe_params(params, mesh: Mesh, axis: str = ROWS):
    """Place every MoE expert tensor with its leading expert axis sharded
    over ``axis`` (router ``wg`` replicated): expert parallelism by data
    placement — XLA's sharding propagation then shards the (E, cap, d)
    expert batches of :func:`moe_ffn` and materializes the token shuffle as
    all_to_all, the same constraint-free idiom the transformer trunk uses
    for sequence sharding (models/transformer.py:_block). Accepts either a
    single :func:`init_moe` dict or a whole transformer params dict (places
    each layer's ``"moe"`` subtree); non-expert leaves pass through."""
    from jax.sharding import NamedSharding

    def place(mp):
        out = dict(mp)
        for k in ("w1", "w2"):
            out[k] = jax.device_put(
                mp[k], NamedSharding(mesh, P(axis, None, None)))
        return out

    if "wg" in params:
        return place(params)
    out = dict(params)
    for k, v in params.items():
        if isinstance(v, dict) and "moe" in v:
            out[k] = dict(v, moe=place(v["moe"]))
    return out


def _route_group(xg, valid, wg, top_k: int, cap: int):
    """One group's routing: returns the (g, E, cap) dispatch / combine
    tensors and the group's load-balance statistics. All routing math in f32.

    Priority is choice-major (every token's first choice outranks all second
    choices), the Switch convention: position-in-expert comes from a cumsum
    over the (k·g, E) choice-flattened one-hots."""
    g = xg.shape[0]
    logits = (xg.astype(jnp.float32) @ wg.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)            # (g, E)
    topv, topi = jax.lax.top_k(gates, top_k)           # (g, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    e = wg.shape[1]
    # (k, g, E) one-hots, masked to live (non-padding) rows
    oh = jax.nn.one_hot(topi.T, e, dtype=jnp.float32) * valid[None, :, None]
    pos = jnp.cumsum(oh.reshape(top_k * g, e), axis=0).reshape(top_k, g, e)
    pos = (pos * oh).astype(jnp.int32)                 # 1-based at selections
    in_cap = pos <= cap                                # pos==0 rows die at one_hot(-1)
    disp_k = jax.nn.one_hot(pos - 1, cap, dtype=jnp.float32) \
        * in_cap[..., None]                            # (k, g, E, cap)
    dispatch = jnp.sum(disp_k, axis=0)                 # (g, E, cap)
    kept = jnp.sum(disp_k, axis=(2, 3))                # (k, g) choice survived?
    w = topv.T * kept                                  # dropped choices: 0
    w = w / jnp.maximum(jnp.sum(w, axis=0, keepdims=True), 1e-9)
    combine = jnp.sum(disp_k * w[:, :, None, None], axis=0)  # (g, E, cap)
    # Switch aux statistics: fraction of (live) tokens whose FIRST choice is
    # expert e, and the mean gate probability per expert
    n_live = jnp.maximum(jnp.sum(valid), 1.0)
    frac = jnp.sum(oh[0], axis=0) / n_live
    mean_gate = jnp.sum(gates * valid[:, None], axis=0) / n_live
    return dispatch, combine, frac, mean_gate


def moe_ffn(mp: dict, x, mesh: Mesh | None = None, axis: str = ROWS,
            top_k: int = 2, capacity_factor: float = 1.25,
            group_size: int = 4096, precision: str = "high",
            remat_groups: bool = True):
    """MoE position-wise FFN over ``x`` (tokens, d) — the drop-in expert
    replacement for the dense GELU FFN of :func:`._mlp`.

    Returns ``(out, aux)``: the combined expert outputs (``x``'s shape and
    dtype) and the scalar Switch load-balance loss
    ``E · Σ_e frac_e · mean_gate_e`` (1.0 = perfectly balanced; add
    ``aux_weight ·`` this to the training loss).

    Expert parallelism is placement-driven: shard the expert params over a
    mesh axis with :func:`shard_moe_params` and XLA's sharding propagation
    shards the (E, cap, d) expert batches to match, materializing the token
    shuffle as all_to_all over ICI — sequence-sharded in, expert-sharded
    compute, sequence-sharded out; no in-function constraints (the same
    idiom the transformer trunk uses for sequence sharding, and what keeps
    eager-mode autodiff placement-clean). ``mesh`` here only validates the
    expert/axis divisibility contract (``E %% mesh.shape[axis] == 0``).
    ``precision`` mirrors the package knob: "high" runs expert matmuls on
    the operands' dtype, "default" narrows them to bf16 (routing always
    f32).
    """
    if precision not in ("high", "default"):
        raise ValueError(f"unknown moe precision: {precision!r}")
    s, d = x.shape
    e = mp["wg"].shape[1]
    if not 1 <= top_k <= e:
        raise ValueError(f"top_k ({top_k}) must be in [1, n_experts={e}]")
    if mesh is not None and e % mesh.shape[axis]:
        raise ValueError(
            f"n_experts ({e}) must be a multiple of mesh axis {axis!r} "
            f"({mesh.shape[axis]}) so each device holds whole experts")
    g = min(group_size, s) if group_size else s
    cap = moe_capacity(g, e, top_k, capacity_factor)
    n_groups = -(-s // g)
    pad = n_groups * g - s

    cd = jnp.bfloat16 if precision == "default" else x.dtype
    w1, w2 = mp["w1"].astype(cd), mp["w2"].astype(cd)

    xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
    live = (jnp.arange(n_groups * g) < s).astype(jnp.float32)
    xg = xp.reshape(n_groups, g, d)
    lg = live.reshape(n_groups, g)

    def one_group(xgi, lgi):
        dispatch, combine, frac, mean_gate = _route_group(
            xgi, lgi, mp["wg"], top_k, cap)
        ein = functools.partial(jnp.einsum, precision="highest",
                                preferred_element_type=jnp.float32)
        xin = ein("gec,gd->ecd", dispatch.astype(cd), xgi.astype(cd))
        h = jax.nn.gelu(ein("ecd,edf->ecf", xin.astype(cd), w1)).astype(cd)
        yo = ein("ecf,efd->ecd", h, w2).astype(cd)
        out = ein("gec,ecd->gd", combine.astype(cd), yo)
        return out.astype(x.dtype), frac, mean_gate

    if n_groups == 1:
        out, frac, mean_gate = one_group(xg[0], lg[0])
        aux = e * jnp.sum(frac * mean_gate)
        return out[:s], aux

    body = lambda _, sl: (None, one_group(*sl))
    if remat_groups:
        body = jax.checkpoint(body)
    _, (outs, fracs, gates) = jax.lax.scan(body, None, (xg, lg))
    # statistics average over groups weighted by live counts ≈ uniform here
    # (only the tail group is short); exactness matters for the loss value,
    # not the gradient direction — weight by each group's live fraction
    wts = jnp.sum(lg, axis=1) / jnp.maximum(jnp.sum(lg), 1.0)
    aux = e * jnp.sum(jnp.sum(fracs * gates, axis=1) * wts)
    return outs.reshape(n_groups * g, d)[:s], aux


def moe_decode_ffn(mp: dict, h, top_k: int = 2):
    """Single-token decode MoE: route one (d,) activation to its top-k
    experts by *gathering* those experts' weights — at one token the dense
    dispatch machinery is pure overhead; two (d, ff) gathers and two matvecs
    are exact and cheap. Used by the decode step when a layer carries MoE
    params. Expert matmuls run in ``h``'s dtype (the decode compute dtype,
    matching the prefill/training cd convention); routing stays f32.
    Returns the combined (d,) output in ``h``'s dtype."""
    gates = jax.nn.softmax(h.astype(jnp.float32) @ mp["wg"].astype(jnp.float32))
    topv, topi = jax.lax.top_k(gates, top_k)
    topv = topv / jnp.sum(topv)
    cd = h.dtype
    w1 = mp["w1"][topi].astype(cd)         # (k, d, ff) gather
    w2 = mp["w2"][topi].astype(cd)         # (k, ff, d)
    hh = jax.nn.gelu(jnp.einsum("d,kdf->kf", h, w1)).astype(cd)
    out = jnp.einsum("kf,kfd->kd", hh, w2)
    return jnp.sum(out * topv[:, None].astype(out.dtype), axis=0).astype(cd)


#: rows of a grouped matmul's tile on the chip, and its weight tile (k, n):
#: the pick wherever both of an expert's widths are whole tiles of 1024
#: (chosen at Laguna's 3072 x 1024 experts, PERF.md section 6, PR 31)
_GMM_TILING = (128, 1024, 1024)


def _gmm_tiling(k: int, n: int, itemsize: int = 2) -> tuple:
    """The grouped matmul's tile ``(rows, k, n)`` for experts of ``(k, n)``,
    from those widths and the operands' ``itemsize`` alone. An expert whose
    widths are both whole tiles of 1024 keeps :data:`_GMM_TILING`. Any other
    (1792 = 14 x 128, 1280 = 10 x 128) takes the whole contraction in ONE
    tile, up to 2048, and 1024 columns: read on the chip at 32 experts of
    2048 x 1792 (``tools/gmm_tiling.py``; PERF.md section 6, PR 44), a
    layer's three matmuls take 1039 us at a decode call's 384 rows and 1293
    at a chunk's 2048, against 1072 and 1441 at ``_GMM_TILING`` (fewer grid
    steps) and 1127 / 1516 at whole tiles of 896 (a quarter-empty tile costs
    less than a narrower one). The rows stay 128 whatever an expert has: an
    expert whose rows straddle a row tile's edge is visited, and its weights
    read, once a tile, and at 12 rows an expert a tile of 16 rows reads most
    experts twice (1686 us; the bytes' time is 860). Operands wider than
    bfloat16 (a float32 check) take as many BYTES of the contraction a
    tile, so half its length: the two buffers of a 2048 x 1024 float32
    weight tile are 16 MB, the whole of the kernel's VMEM
    (``RESOURCE_EXHAUSTED``; PERF.md section 7, PR 44). An expert of at
    most 1280 columns (ten lane tiles) is ONE column tile: read at 40
    experts of 4096 x 1280 (PERF.md section 6, PR 51), a second tile of 1024
    that is three quarters empty costs a layer 1798 us at a decode call's
    128 rows and 2152 at a chunk's 512 where the whole width takes 1594 and
    1912 (the bytes' time is 1383 / 1536)."""
    tile = _GMM_TILING if k % 1024 == 0 and n % 1024 == 0 else (
        _GMM_TILING[0], min(k, 2048), n if n <= 1280 else 1024)
    if itemsize <= 2:
        return tile
    return (tile[0], max(128, tile[1] * 2 // itemsize // 128 * 128), tile[2])


def _grouped_matmul(xs, w, sizes, out_dtype, tiling=None):
    """``xs[rows of group e] @ w[e]`` for rows sorted by group, ``sizes`` rows
    a group; rows past the last group belong to none and come back
    UNDEFINED (the caller never reads them). On the chip this is the Pallas
    grouped matmul that ships with JAX (``megablox.gmm``) with 1024 x 1024
    weight tiles: XLA's own lowering of ``jax.lax.ragged_dot`` picks 512 x
    512, which at a prefill chunk's ~40 rows a group is bound by its grid
    steps (1.3 ms a call where the weights' bytes take 0.5; ``PERF.md``
    section 6, PR 31). The tile is :func:`_gmm_tiling`'s (``tiling``: the
    tool's to vary; rows are padded to its row tile). Both visit only the groups that have rows. Elsewhere
    (the CPU tests) ``ragged_dot``'s reference lowering."""
    from ..ops.pallas_kernels import _interpret

    if _interpret():
        return jax.lax.ragged_dot(xs, w, sizes,
                                  preferred_element_type=out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    tiling = tiling or _gmm_tiling(w.shape[1], w.shape[2],
                                   jnp.dtype(w.dtype).itemsize)
    pad = -xs.shape[0] % tiling[0]
    out = gmm(jnp.pad(xs, ((0, pad), (0, 0))) if pad else xs, w, sizes,
              preferred_element_type=out_dtype, tiling=tiling)
    return out[:xs.shape[0]]


def _picks(logits, mp: dict, top_k: int, scoring: str, renorm_eps: float,
           n_group: int = 1, topk_group: int = 1):
    """The router's picks from its float32 outputs ``logits`` (T, E):
    ``(weights (T, k), experts (T, k))`` as :func:`moe_experts_ffn`
    describes them, the weights renormalised over the picks. With
    ``n_group`` > 1 (sigmoid scoring) the picks are group-limited: the
    experts lie in ``n_group`` groups of consecutive ones, a group scores the
    sum of its TWO largest biased scores, and only the ``topk_group`` best
    groups' experts can be picked."""
    if n_group > 1 and scoring != "sigmoid":
        raise ValueError("group-limited picks are the sigmoid scoring's")
    if scoring == "softmax":
        topv, topi = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores + mp["e_bias"]
        if n_group > 1:
            T, E = choice.shape
            best2, _ = jax.lax.top_k(choice.reshape(T, n_group, E // n_group),
                                     2)
            _, kept = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
            stays = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None,
                                                                   None, :],
                            axis=1)                               # (T, groups)
            choice = jnp.where(jnp.repeat(stays, E // n_group, axis=1),
                               choice, -jnp.inf)
        _, topi = jax.lax.top_k(choice, top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
    else:
        raise ValueError(f"unknown expert scoring {scoring!r}")
    total = jnp.sum(topv, axis=-1, keepdims=True)
    return topv / (total + renorm_eps if renorm_eps else total), topi


def moe_experts_ffn(mp: dict, h, valid, *, top_k: int, first_expert: int = 0,
                    routed_scale: float = 1.0, compute_dtype=None,
                    scoring: str = "softmax", renorm_eps: float = 0.0,
                    n_group: int = 1, topk_group: int = 1):
    """The dropless expert layer of the serving path, for a ``(T, d)`` batch
    of tokens — a prefill chunk or a decode bucket's rows alike, so both
    route identically: float32 softmax router over ALL the model's experts
    (``mp["router"]``: (d, E)), the ``top_k`` largest renormalised over
    themselves, SwiGLU experts, the routed sum times ``routed_scale``, plus
    the shared expert (ungated) where the model has one (``mp["s_gate"]``,
    ``s_up``, ``s_down``; a layer without them adds nothing and runs no
    matmul for it). The layer holds the experts
    ``[first_expert, first_expert + held)`` (``mp["e_gate"]``/``e_up``:
    (held, d, f), ``e_down``: (held, f, d)) and computes THEIR part of the
    result: what the absent experts would add is left out. There is no
    capacity and no dropped token: the assignments are sorted by held expert
    and the experts run as grouped matmuls (:func:`_grouped_matmul`, which
    visits only the groups that have rows, so an expert no token picked is
    never read). Rows where ``valid``
    is false (a bucket's dummy rows, a chunk's padding) are routed nowhere.

    ``scoring`` (static) ``sigmoid``: each router output through a sigmoid;
    the picks are the ``top_k`` largest of score + ``mp["e_bias"]`` (E,), a
    per-expert bias that only selects; the weights are the picks' own scores
    renormalised over themselves (``renorm_eps`` is added to their sum
    first, where a family's implementation does). ``n_group`` /
    ``topk_group`` (static) limit the picks to the best groups
    (:func:`_picks`); (1, 1) is no limit.

    The router reads ``h`` as it comes (float32 from the caller's norm: a
    rounded input flips near-ties among the picks); the experts' matmuls run
    on ``h`` in ``compute_dtype`` (default: ``h``'s own).

    Returns ``(out, counts)``: ``out`` (T, d) in ``h``'s dtype; ``counts``
    int32 ``(assignments, assignments that fell on held experts, distinct
    held experts touched)``."""
    T, d = h.shape
    cd = jnp.dtype(compute_dtype or h.dtype)
    held = mp["e_gate"].shape[0]
    logits = jnp.matmul(h.astype(jnp.float32),
                        mp["router"].astype(jnp.float32), precision="highest")
    topv, topi = _picks(logits, mp, top_k, scoring, renorm_eps, n_group,
                        topk_group)                                # (T, k)
    local = ((topi >= first_expert) & (topi < first_expert + held)
             & valid[:, None])
    # one row per assignment, sorted by held expert; what is not held here
    # (or not a token) sorts behind every group and belongs to none
    lid = jnp.where(local, topi - first_expert, held).reshape(T * top_k)
    order = jnp.argsort(lid, stable=True)
    sizes = jnp.sum(lid[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    hc = h.astype(cd)
    xs = hc[order // top_k]                                       # (A, d)
    a = (jax.nn.silu(_grouped_matmul(xs, mp["e_gate"].astype(cd), sizes, cd))
         * _grouped_matmul(xs, mp["e_up"].astype(cd), sizes, cd))
    ys = _grouped_matmul(a, mp["e_down"].astype(cd), sizes,
                         jnp.float32)                             # (A, d)
    n_local = jnp.sum(sizes)
    # back to the tokens, a pick at a time: a local pick's row lies inside
    # its group; every other pick's row is undefined and is not read
    at = jnp.argsort(order).reshape(T, top_k)
    routed = sum(jnp.where(local[:, j, None], ys[at[:, j]], 0.0)
                 * topv[:, j, None] for j in range(top_k))
    out = routed_scale * routed
    if "s_gate" in mp:
        out = out + jnp.matmul(jax.nn.silu(hc @ mp["s_gate"].astype(cd))
                               * (hc @ mp["s_up"].astype(cd)),
                               mp["s_down"].astype(cd),
                               preferred_element_type=jnp.float32)
    counts = jnp.stack([jnp.sum(valid) * top_k, n_local,
                        jnp.sum(sizes > 0)]).astype(jnp.int32)
    return out.astype(h.dtype), counts
