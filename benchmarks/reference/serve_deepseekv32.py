"""The plain reference of the ``serve_deepseekv32`` cells: the decoder that the
configuration file describes (DeepSeek-V3.2's published keys, the
``deepseek_v32`` configuration family: latent attention over the TOKENS a
lightning indexer selects, group-limited sigmoid routing), written from the
equations below in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``; no page, no kernel, no batching,
no chunk: the index scores of every query against every position it can
see, its ``index_topk`` largest taken exactly (:func:`largest`: ``lax.top_k``'s
set, found without the sort), and the
selection as a MASK inside plain causal attention per head, unabsorbed (at
the cell's 66 k of context, where that form does not fit a run, each query's
selected latents gathered and met absorbed: :func:`layer`). Nothing of ``marlin_tpu`` is imported. The weights it is given
are the program's own (bfloat16, the held experts, the held slice of the
vocabulary), upcast a layer, a head or an expert at a time.

A sequence is met a SEGMENT of ``segment`` positions at a time, front to
back, every layer over the segment before the next segment: the rows of the
residual stream, the queries and the selections exist for one segment only
(67 k positions of 7168 floats and of 128 heads do not fit beside the
weights), and what a later segment needs of an earlier one is its KEYS: the
normed latent, the rotary key and the index key of every position and layer,
exactly what a full pass computes for them (a layer's output at a position
depends on earlier positions alone). ``memo`` keeps those keys by the tokens
before a segment's end: two sequences that begin with the same document give
the same keys there, and a second full pass would compute them again.

``x`` is the residual stream; ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) *
g``. One layer, H heads, for a token ``t``::

    h      = rmsnorm(x, g1)
    c_q    = rmsnorm(h W_qa, g_q)
    q      = c_q W_qb                  -> H x [q_nope | q_pe]
    [c_kv | k_pe] = h W_kva            (k_pe: ONE vector a token, all heads)
    c_kv   = rmsnorm(c_kv, g_kv)
    q_pe, k_pe <- rope at the token's position (adjacent pairs turned
                  together: ``rope_interleave``)
    indexer:
    q^I_j  = (c_q W^I_qb)_j            j = 1..J, D wide
    k^I    = LayerNorm(h W^I_k)        gain and bias, ONE head
    q^I_j, k^I <- rope on their first ``qk_rope_head_dim`` columns,
                  ROTATE-HALF (column i with i + r/2), the same frequencies
    w_j    = (h W^I_w)_j J^-1/2 D^-1/2
    I(t,s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s))          s <= t
    S_t    = the min(k, t + 1) positions s <= t of largest I(t,s), ties to
             the lower position
    [k_nope_h | v_h] = c_kv W_kvb[h]
    a_h(t, s) = scale (q_nope_h(t) . k_nope_h(s) + q_pe_h(t) . k_pe(s))
                for s in S_t
    o_h    = softmax_{s in S_t}(a_h) v_h ;  x <- x + concat_h(o_h) W_o

rope: YaRN as ``reference/serve_mistral4.py`` writes it, from
``rope_scaling`` and ``rope_theta``; ``scale = (nope + rope)^-1/2
m(mscale_all_dim)^2`` with ``m(k) = 0.1 k ln(factor) + 1``; cos and sin times
``m(mscale) / m(mscale_all_dim)`` (1 here).

Expert layer: ``h2 = rmsnorm(x, g2)``; ``sc = sigmoid(h2 W_r)`` over all the
model's experts; ``c = sc + b``; the experts lie in ``n_group`` groups of
consecutive ones; a group scores the sum of its TWO largest ``c``; the
``topk_group`` best groups stay; ``P`` = the ``top_k`` largest ``c`` among
their experts; ``w_i = sc_i / sum_{j in P} sc_j``; ``x <- x + scale * sum_{i
in P, i held here} w_i E_i(h2) + E_shared(h2)`` with ``E(h) = (silu(h Wg) *
(h Wu)) Wd``. A layer before ``first_k_dense_replace`` has the dense SwiGLU
instead. Head: ``logits = rmsnorm(x, g_f) W_head^T`` over the held rows.

ASSUMED (the configuration file gives each reason): see :data:`ASSUMED`.

``quant`` puts a lower precision in the reference's place (the control):
every matmul operand goes through it first. ``flaw`` leaves one piece of the
mathematics out (:data:`FLAWS`), for the tests that show the comparison
catches it.
"""

from __future__ import annotations

import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

ASSUMED = ("indexer_rope_rotate_half", "indexer_layernorm_eps",
           "latent_norms", "rope_interleave_main", "mscale_squared_on_scale",
           "sigmoid_scoring_selection_bias", "group_score_sum_of_two")
#: what each leaves out or bends; every one moves the logits of a context
#: past ``index_topk``
FLAWS = ("no_relu", "no_head_weights", "no_key_norm", "no_key_norm_bias",
         "indexer_rope_interleaved", "future_selectable", "topk_minus_one",
         "dense_attention", "selection_of_previous", "stale_index_keys",
         "no_group_limit")
#: ``stale_index_keys``: the index keys of these positions read zeros, as a
#: page copied, shared or evicted WITHOUT its index keys would
STALE = (8, 16)
SEGMENT = 4096
_QUERY_BLOCK = 1024
_LIST_BLOCK = 256    # x index_topk x 128 counts stand at once (listed)
_MASKED = -1e30


def fp8_operand(x):
    """Per-tensor scaled float8 (e4m3) and back, in plain arithmetic (copied
    from ``reference/serve.py``): the nearest precision below bfloat16."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    y = x / scale
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    ulp = jnp.exp2(e - 3.0)
    return jnp.round(y / ulp) * ulp * scale


def _identity(x):
    return x


def rope_parameters(cfg: dict) -> dict:
    """The family's published pair (``rope_scaling``, ``rope_theta``) as one
    dict, ``type`` under the name ``rope_type``."""
    rp = {**(cfg.get("rope_scaling") or {}), "rope_theta": cfg["rope_theta"]}
    rp["rope_type"] = rp.pop("type", "default")
    return rp


def describe(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration file alone."""
    share = cfg.get("deployment_share", {})
    return {
        "n_layers": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope_dim": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "ix_heads": int(cfg["index_n_heads"]),
        "ix_dim": int(cfg["index_head_dim"]),
        "topk": int(cfg["index_topk"]),
        "ix_eps": float(cfg.get("index_norm_eps", 1e-6)),
        "dense_first": int(cfg.get("first_k_dense_replace", 0)),
        "interleave": bool(cfg.get("rope_interleave", False)),
        "rope": tuple(sorted(rope_parameters(cfg).items())),
        "held": int(cfg["n_routed_experts"]),
        "first": int(share.get("first_expert", 0)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "n_group": int(cfg.get("n_group", 1)),
        "topk_group": int(cfg.get("topk_group", 1)),
        "scale": float(cfg.get("routed_scaling_factor", 1.0)),
        "eps": float(cfg["rms_norm_eps"])}


def inv_freq(rope: dict, D: int) -> np.ndarray:
    theta = float(rope["rope_theta"])
    i = np.arange(0, D, 2, dtype=np.float64)
    extra = 1.0 / theta ** (i / D)
    if rope.get("rope_type", "default") == "default":
        return extra.astype(np.float32)
    inter = extra / float(rope["factor"])

    def c(r):
        return (D * math.log(rope["original_max_position_embeddings"]
                             / (r * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), D - 1)
    ramp = np.clip((np.arange(D // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def yarn_m(rope: dict, k: float) -> float:
    """``0.1 k ln(factor) + 1`` (1 without YaRN)."""
    if rope.get("rope_type", "default") != "yarn" or rope["factor"] <= 1:
        return 1.0
    return 0.1 * k * math.log(rope["factor"]) + 1.0


def apply_rope(x, pos, rope: dict, interleave: bool):
    """``x`` (T, heads, D) at positions ``pos`` (T,), every column turned."""
    D = x.shape[-1]
    f = jnp.asarray(inv_freq(rope, D))
    ang = pos.astype(jnp.float32)[:, None] * f[None, :]
    factor = (yarn_m(rope, rope.get("mscale", 1.0))
              / yarn_m(rope, rope.get("mscale_all_dim", 0.0)))
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    if interleave:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(w):
    return w.astype(jnp.float32)


def largest(scores, can, k: int):
    """``lax.top_k``'s SET without its sort (a sort of 67 k scores a query is
    most of a pass at the cell's context): the mask (Q, L) of the ``min(k,
    visible)`` positions of largest ``scores`` (Q, L) float32 among those
    ``can`` allows, ties to the lower position. The k-th largest score is
    found exactly, by bisection on the scores' bit patterns (an order-keeping
    map of float32 onto the unsigned integers, one bit a pass); every larger
    score is taken, and of the scores equal to it the lowest positions that
    still fit."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    u = jnp.where(can, u, jnp.uint32(0))   # below every real score's image
    want = jnp.minimum(jnp.sum(can, axis=-1), k)

    def bit(i, kth):    # keep a bit where `want` scores still reach the value
        trial = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        reach = jnp.sum(u >= trial[:, None], axis=-1)
        return jnp.where(reach >= want, trial, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[0], jnp.uint32))
    above = u > kth[:, None]
    tied = (u == kth[:, None]) & can
    room = want - jnp.sum(above, axis=-1)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= room[:, None]))


def listed(mask, k: int):
    """A mask (Q, L) of at most ``k`` positions a row as ``(idx (Q, k) int32,
    took (Q, k) bool)``: the positions ascending, ``took`` false on the
    places past a row's count. By counts and not by a sort: place ``j``
    lies in the group of ``g`` consecutive positions at which the running
    count passes ``j``, and is that group's set position number ``j`` less
    the count before the group."""
    Q, L = mask.shape
    g = math.gcd(L, 128)
    groups = mask.reshape(Q, L // g, g).astype(jnp.int32)
    ends = jnp.cumsum(jnp.sum(groups, axis=-1), axis=-1)        # (Q, L / g)
    place = jnp.arange(k)
    took = place[None, :] < ends[:, -1:]
    group = jnp.minimum(jnp.sum(ends[:, None, :] <= place[None, :, None],
                                axis=-1), L // g - 1)           # (Q, k)
    mine = jnp.take_along_axis(groups, group[:, :, None], axis=1)  # (Q, k, g)
    before = jnp.take_along_axis(ends, group, axis=1) - jnp.sum(mine, axis=-1)
    nth = (place[None, :] - before + 1)[:, :, None]
    inside = jnp.argmax((jnp.cumsum(mine, axis=-1) == nth) & (mine == 1),
                        axis=-1)
    return jnp.where(took, group * g + inside, 0).astype(jnp.int32), took


@functools.partial(jax.jit, static_argnames=("dense", "dims", "quant", "flaw",
                                             "gathered"),
                   donate_argnums=(2,))
def layer(x, lp, keys, n0, dense: bool, dims, quant=_identity,
          flaw: str = "", gathered: bool = False):
    """One layer over ONE segment: ``x`` (T, d) float32, the residual stream
    of positions ``n0 .. n0 + T - 1``; ``keys`` = ``(c_kv (L, rank), k_pe (L,
    r), k_ix (L, D))``, every earlier position's filled in, this segment's
    written here (``n0`` is a multiple of T). Returns ``(x, keys, picks)``;
    ``picks`` = ``(idx (T, k) int32, took (T, k) bool)``: each query's
    selection, ascending, and which of its places hold a position.

    The attention's two forms compute the same numbers. The default is the
    equations as written: a head at a time, its keys and values up-projected
    for every position, a plain softmax under the selection as a MASK.
    ``gathered`` is for contexts at which that costs 81,920 operations a
    (query, key) pair of the WHOLE context in float32 (880 TFLOP a document
    of 65,536 tokens and five layers, half a minute of the chip at its peak,
    where a run has six): each query's selected latents are gathered and met
    in the absorbed form, the query through the key half of ``W_kvb`` and the
    value half applied to the attended latent (``tests`` hold the two forms
    to each other)."""
    m = dict(dims)
    T, L = x.shape[0], keys[0].shape[0]
    H, n, r, vd = m["heads"], m["nope"], m["rope_dim"], m["v_dim"]
    J, D, rank, eps = m["ix_heads"], m["ix_dim"], m["kv_rank"], m["eps"]
    rope = dict(m["rope"])
    topk = min(m["topk"] - (flaw == "topk_minus_one"), L)
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    pos = n0 + jnp.arange(T)
    h = rmsnorm(x, _f32(lp["ln1"]), eps)
    c_q = rmsnorm(mm(h, lp["wq_a"]), _f32(lp["q_norm"]), eps)
    kv = mm(h, lp["wkv_a"])
    c_kv = rmsnorm(kv[:, :rank], _f32(lp["kv_norm"]), eps)
    k_pe = apply_rope(kv[:, None, rank:], pos, rope, m["interleave"])[:, 0]
    ix_turn = flaw == "indexer_rope_interleaved"

    def turned(v, at):   # (T', heads, D): the first r columns, rotate-half
        return jnp.concatenate(
            [apply_rope(v[..., :r], at, rope, ix_turn), v[..., r:]], axis=-1)

    k_ix = mm(h, lp["ix_wk"])
    if flaw != "no_key_norm":
        mean = jnp.mean(k_ix, axis=-1, keepdims=True)
        var = jnp.mean((k_ix - mean) ** 2, axis=-1, keepdims=True)
        k_ix = (k_ix - mean) * jax.lax.rsqrt(var + m["ix_eps"]) * _f32(
            lp["ix_k_gain"])
        if flaw != "no_key_norm_bias":
            k_ix = k_ix + _f32(lp["ix_k_bias"])
    k_ix = turned(k_ix[:, None, :], pos)[:, 0]
    keys = tuple(jax.lax.dynamic_update_slice(old, new, (n0, 0))
                 for old, new in zip(keys, (c_kv, k_pe, k_ix)))
    c_all, pe_all, ix_all = keys
    if flaw == "stale_index_keys":
        ix_all = ix_all.at[STALE[0]:STALE[1]].set(0.0)
    w_ix = mm(h, lp["ix_w"]) * (J * D) ** -0.5
    if flaw == "no_head_weights":
        w_ix = jnp.ones_like(w_ix) * (J * D) ** -0.5
    wq_ix = _f32(lp["ix_wq_b"]).reshape(-1, J, D)
    qb = min(_QUERY_BLOCK, T)
    at = jnp.arange(L)
    ix_blocks = quant(ix_all).reshape(L // T, T, D)

    def select(b):   # a block of queries: index scores, then the largest
        rows = b * qb + jnp.arange(qb)
        cq, p_rows = quant(c_q[rows]), pos[rows]

        def key_block(kb, scores):   # the keys a segment's worth at a time:
            keys_b = ix_blocks[kb]   # none after this segment is computed

            def head(j, acc):
                wj = jax.lax.dynamic_index_in_dim(wq_ix, j, 1, keepdims=False)
                qj = turned(jnp.matmul(cq, quant(wj))[:, None, :],
                            p_rows)[:, 0]
                sc = jnp.matmul(quant(qj), keys_b.T)            # (qb, T)
                if flaw != "no_relu":
                    sc = jnp.maximum(sc, 0.0)
                return acc + jax.lax.dynamic_index_in_dim(
                    w_ix[rows], j, 1) * sc

            part = jax.lax.fori_loop(0, J, head,
                                     jnp.zeros((qb, T), jnp.float32))
            return jax.lax.dynamic_update_slice(scores, part, (0, kb * T))

        scores = jax.lax.fori_loop(0, n0 // T + 1, key_block,
                                   jnp.zeros((qb, L), jnp.float32))
        seen = at[None, :] <= p_rows[:, None]
        can = (at[None, :] < n0 + T) if flaw == "future_selectable" else seen
        mask = largest(scores, can, topk)
        lb = min(_LIST_BLOCK, qb)
        idx, took = jax.lax.map(lambda m: listed(m, topk),
                                mask.reshape(qb // lb, lb, L))
        return idx.reshape(qb, topk), took.reshape(qb, topk)

    idx, took = jax.lax.map(select, jnp.arange(T // qb))
    idx, took = idx.reshape(T, topk), took.reshape(T, topk)
    picks = (idx, took)
    if flaw == "selection_of_previous":
        idx, took = (jnp.concatenate([a[:1], a[:-1]]) for a in (idx, took))
    scale = (n + r) ** -0.5 * yarn_m(rope, rope.get("mscale_all_dim", 0.0)) ** 2
    wq = _f32(lp["wq_b"]).reshape(-1, H, n + r).transpose(1, 0, 2)
    wkv = _f32(lp["wkv_b"]).reshape(rank, H, n + vd).transpose(1, 0, 2)
    wo = _f32(lp["wo"]).reshape(H, vd, -1)
    cq_all = quant(c_q)
    if gathered:
        if flaw == "dense_attention":
            raise ValueError("dense_attention is a flaw of the masked form")
        gb = min(64, T)   # queries whose gathered latents stand at once
        both = jnp.concatenate([c_all, pe_all], axis=-1)

        def block(b):
            rows = b * gb + jnp.arange(gb)
            q = jnp.einsum("tc,hcd->thd", cq_all[rows], quant(wq))
            q_pe = apply_rope(q[..., n:], pos[rows], rope, m["interleave"])
            qt = jnp.einsum("thn,hcn->thc", quant(q[..., :n]),
                            quant(wkv[..., :n]))                # absorbed
            ent = both[idx[rows]]        # ONE gather of [c_kv | k_pe] rows
            ent_c, ent_pe = quant(ent[..., :rank]), quant(ent[..., rank:])
            s = (jnp.einsum("thc,tkc->thk", quant(qt), ent_c)
                 + jnp.einsum("thr,tkr->thk", quant(q_pe), ent_pe)) * scale
            p = jax.nn.softmax(
                jnp.where(took[rows][:, None, :], s, _MASKED), axis=-1)
            ot = jnp.einsum("thk,tkc->thc", quant(p), ent_c)
            o = jnp.einsum("thc,hcv->thv", quant(ot), quant(wkv[..., n:]))
            return jnp.einsum("thv,hvd->td", quant(o), quant(wo))

        x = x + jax.lax.map(block, jnp.arange(T // gb)).reshape(T, -1)
    else:
        took_mask = jnp.zeros((T, L), bool).at[
            jnp.arange(T)[:, None], idx].max(took)
        if flaw == "dense_attention":
            took_mask = at[None, :] <= pos[:, None]

        def one_head(acc, hw):   # plain attention of one head under the mask
            wq_h, wkv_h, wo_h = hw
            q = jnp.matmul(cq_all, quant(wq_h))                 # (T, n + r)
            q_pe = apply_rope(q[:, None, n:], pos, rope,
                              m["interleave"])[:, 0]
            up = jnp.matmul(quant(c_all), quant(wkv_h))         # (L, n + vd)
            s = (jnp.matmul(quant(q[:, :n]), quant(up[:, :n]).T)
                 + jnp.matmul(quant(q_pe), quant(pe_all).T)) * scale
            p = jax.nn.softmax(jnp.where(took_mask, s, _MASKED), axis=-1)
            o = jnp.matmul(quant(p), quant(up[:, n:]))
            return acc + jnp.matmul(quant(o), quant(wo_h)), None

        x, _ = jax.lax.scan(one_head, x, (wq, wkv, wo))
    h = rmsnorm(x, _f32(lp["ln2"]), eps)
    if dense:
        return x + mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
                      lp["w_down"]), keys, picks
    mp = lp["moe"]
    sc = jax.nn.sigmoid(jnp.matmul(h, _f32(mp["router"])))
    choice = sc + mp["e_bias"]
    G = m["n_group"]
    if G > 1 and flaw != "no_group_limit":
        grouped = choice.reshape(T, G, -1)
        two = jnp.sort(grouped, axis=-1)[..., -2:].sum(axis=-1)   # (T, G)
        _, kept = jax.lax.top_k(two, m["topk_group"])
        stays = jnp.zeros((T, G), bool).at[jnp.arange(T)[:, None],
                                           kept].set(True)
        choice = jnp.where(jnp.repeat(stays, grouped.shape[-1], axis=1),
                           choice, -jnp.inf)
    _, topi = jax.lax.top_k(choice, m["top_k"])
    topv = jnp.take_along_axis(sc, topi, axis=-1)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(topi - m["first"], m["held"],
                                    dtype=jnp.float32) * topv[..., None],
                     axis=1)

    def expert(acc, e):  # one expert's weights upcast at a time
        wg, wu, wd, we = e
        y = mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)
        return acc + we[:, None] * y, None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             (mp["e_gate"], mp["e_up"], mp["e_down"],
                              weight.T))
    shared = mm(jax.nn.silu(mm(h, mp["s_gate"])) * mm(h, mp["s_up"]),
                mp["s_down"])
    return x + m["scale"] * routed + shared, keys, picks


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, ln_f, w_head, eps, quant=_identity):
    return jnp.matmul(quant(rmsnorm(x, _f32(ln_f), eps)),
                      quant(_f32(w_head)).T)


def _dims(cfg: dict):
    return tuple(sorted(describe(cfg).items()))


def forward(params: dict, cfg: dict, tokens, pad_to: int, quant=_identity,
            flaw: str = "", segment: int | None = None, memo=None,
            want=None, gathered: bool = False):
    """A full pass over ``tokens`` (1-D ints), a segment at a time. Yields
    ``(first position, x (segment, d), picks)`` a segment: the residual
    stream after the last layer and every layer's selections
    (:func:`layer`). With ``want`` (positions) only the segments that hold
    one are yielded; the others only leave their keys. ``pad_to`` (rounded up
    to whole segments) fixes the keys' length, so every call has one shape;
    the padding lies after every real position and is causally invisible.
    ``memo`` (a dict) keeps, by the tokens before a segment's end, every
    layer's keys of that segment: a sequence that begins with tokens seen
    before takes them from there."""
    m, dims = describe(cfg), _dims(cfg)
    seg = int(segment or SEGMENT)
    n = len(tokens)
    L = -(-max(pad_to, n) // seg) * seg
    toks = np.zeros(L, np.int32)
    toks[:n] = tokens
    widths = (m["kv_rank"], m["rope_dim"], m["ix_dim"])
    keys = [tuple(jnp.zeros((L, w), jnp.float32) for w in widths)
            for _ in range(m["n_layers"])]
    digest = hashlib.sha1()
    with jax.default_matmul_precision("highest"):
        for n0 in range(0, -(-n // seg) * seg, seg):
            digest.update(toks[n0:n0 + seg].tobytes())
            tag = (digest.hexdigest(), L, flaw, quant is _identity)
            wanted = want is None or any(n0 <= p < n0 + seg for p in want)
            if memo is not None and tag in memo and not wanted:
                keys = [tuple(jax.lax.dynamic_update_slice(old, new, (n0, 0))
                              for old, new in zip(ks, news))
                        for ks, news in zip(keys, memo[tag])]
                continue
            x = _f32(jnp.take(params["emb"],
                              jnp.asarray(toks[n0:n0 + seg]), axis=0))
            picks = []
            for i in range(m["n_layers"]):
                x, keys[i], pk = layer(
                    x, params[f"l{i}"], keys[i], jnp.int32(n0),
                    dense=i < m["dense_first"], dims=dims, quant=quant,
                    flaw=flaw, gathered=gathered)
                picks.append(pk)
            if memo is not None and n0 + seg <= n:
                memo[tag] = [tuple(k[n0:n0 + seg] for k in ks)
                             for ks in keys]
            if wanted:
                yield n0, x, picks


def kept_index_keys(memo: dict, tokens, pad_to: int,
                    segment: int | None = None) -> list:
    """What ``memo`` keeps of the index keys of ``tokens``' leading whole
    segments after a sound pass over them (:func:`forward`): a list a layer
    of ``(positions, index_head_dim)`` float32, as many positions as the
    memo holds from the front."""
    seg = int(segment or SEGMENT)
    toks = np.asarray(tokens, np.int32)
    L = -(-max(pad_to, len(toks)) // seg) * seg
    digest, kept = hashlib.sha1(), []
    for n0 in range(0, len(toks) // seg * seg, seg):
        digest.update(toks[n0:n0 + seg].tobytes())
        got = memo.get((digest.hexdigest(), L, "", True))
        if got is None:
            break
        kept.append([np.asarray(ks[2]) for ks in got])
    return [np.concatenate(layer) for layer in zip(*kept)]


def logits_at(params: dict, cfg: dict, tokens, positions, pad_to: int,
              quant=_identity, flaw: str = "", segment: int | None = None,
              memo=None, gathered: bool = False):
    """Float32 logits over the held vocabulary after the given ``positions``
    of ``tokens``, ``(len(positions), vocab)``."""
    positions = np.asarray(positions)
    eps = describe(cfg)["eps"]
    out = np.zeros((len(positions), params["head"].shape[0]), np.float32)
    for n0, x, _ in forward(params, cfg, tokens, pad_to, quant, flaw,
                            segment, memo, set(positions.tolist()), gathered):
        here = (positions >= n0) & (positions < n0 + x.shape[0])
        with jax.default_matmul_precision("highest"):
            full = head(x, params["ln_f"], params["head"], eps=eps,
                        quant=quant)
        out[here] = np.asarray(full)[positions[here] - n0]
    return out


def selections(params: dict, cfg: dict, tokens, pad_to: int,
               segment: int | None = None) -> list:
    """Every layer's selection of every position of ``tokens``: a list a
    layer of lists a position of sorted positions (the tests' sizes)."""
    per_layer = [[] for _ in range(describe(cfg)["n_layers"])]
    for n0, _, picks in forward(params, cfg, tokens, pad_to,
                                segment=segment):
        for sets, (idx, took) in zip(per_layer, picks):
            idx, took = np.asarray(idx), np.asarray(took)
            sets.extend(sorted(i[t].tolist()) for i, t in zip(idx, took))
    return [sets[:len(tokens)] for sets in per_layer]


def served_gaps(params: dict, cfg: dict, tokens, n_prompt: int, pad_to: int,
                max_out: int, control: bool = False, flaw: str = "",
                segment: int | None = None, memo=None,
                gathered: bool = False) -> dict:
    """For one served request (``tokens`` = prompt + served tokens): at every
    served position, how far the served token's reference logit lies below
    the reference's best. With ``control``, the same for the token that the
    float8 control puts first at that position."""
    tokens = np.asarray(tokens, np.int64)
    n_out = len(tokens) - n_prompt
    pos = np.arange(n_prompt - 1, len(tokens) - 1)
    ref = logits_at(params, cfg, tokens[:-1], pos, pad_to, flaw=flaw,
                    segment=segment, memo=memo, gathered=gathered)
    best = ref.max(axis=-1)
    served = tokens[n_prompt:]
    out = {"gaps": best - ref[np.arange(n_out), served],
           "argmax_agree": float((ref.argmax(-1) == served).mean())}
    if control:
        low = logits_at(params, cfg, tokens[:-1], pos, pad_to,
                        quant=fp8_operand, segment=segment, memo=memo,
                        gathered=gathered)
        out["control_gaps"] = best - ref[np.arange(n_out), low.argmax(-1)]
    return out
