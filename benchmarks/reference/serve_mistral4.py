"""The plain reference of the ``serve_mistral4`` cells: the decoder that the
configuration file describes (Mistral-Small-4-119B-2603's published keys,
the DeepSeek-V3 configuration family), written from the equations below in
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``;
no cache, no kernel, no batching, NOT absorbed: the up-projection is applied
to every position's latent and plain causal attention runs per head, one full
forward over a whole sequence. Nothing of ``marlin_tpu`` is imported. The
weights it is given are the program's own (bfloat16, the held experts, the
held slice of the vocabulary); they are upcast a layer, or an expert, at a
time. Attention runs a head and a block of queries at a time and the expert
layer an expert at a time, so that 17,920 positions fit beside the weights.

``x`` is the residual stream; ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) *
g``. One layer, H heads::

    h      = rmsnorm(x, g1)
    c_q    = rmsnorm(h W_qa, g_q)
    q      = c_q W_qb                  -> H x [q_nope | q_pe]
    [c_kv | k_pe] = h W_kva            (k_pe: ONE vector a token, all heads)
    c_kv   = rmsnorm(c_kv, g_kv)
    q_pe, k_pe <- rope at the token's position
    [k_nope_h | v_h] = c_kv W_kvb[h]
    a_h(i, j) = s tau(i) (q_nope_h(i) . k_nope_h(j) + q_pe_h(i) . k_pe(j))
                for j <= i
    o_h    = softmax_j(a_h) v_h ;  x <- x + concat_h(o_h) W_o

rope: the ``qk_rope_head_dim`` columns only, adjacent pairs ``(2i, 2i+1)``
turned together (``rope_interleave``), YaRN: ``extra_i = theta^(-2i/D)``,
``inter_i = extra_i / factor``; ``c(r) = D ln(original_max / (2 pi r)) / (2
ln theta)``, ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))``
clamped to ``[0, D-1]``; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
``inv_freq_i = inter_i ramp_i + extra_i (1 - ramp_i)``; cos and sin times
``m(mscale) / m(mscale_all_dim)`` with ``m(k) = 0.1 k ln(factor) + 1``.
``s = (nope + rope)^-1/2 m(mscale_all_dim)^2``; ``tau(i) = 1 + beta ln(1 +
floor(i / original_max))``, ``beta = llama_4_scaling_beta``.

Expert layer: ``h2 = rmsnorm(x, g2)``; ``sc = sigmoid(h2 W_r)`` over all the
model's experts; ``I`` = the ``top_k`` largest of ``sc + b``; ``w_i = sc_i /
sum_{j in I} sc_j``; ``E_i(h) = (silu(h Wg_i) * (h Wu_i)) Wd_i``; ``x <- x +
scale * sum_{i in I, i held here} w_i E_i(h2) + E_shared(h2)``. What the
absent experts would add is left out. A layer before
``first_k_dense_replace`` has the dense SwiGLU of width ``intermediate_size``
instead. Head: ``logits = rmsnorm(x, g_f) W_head^T`` over the held rows.

ASSUMED (the configuration file gives each reason): sigmoid scoring with a
selection-only bias; the norms on both latents; ``m^2`` on the softmax scale;
``tau``'s form; the rotary embedding on the ``qk_rope`` columns only.

``quant`` puts a lower precision in the reference's place (the control):
every matmul operand goes through it first. ``flaws`` leaves one piece of
the mathematics out, for the tests that show the comparison catches it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ASSUMED = ("sigmoid_scoring_selection_bias", "latent_norms",
           "mscale_squared_on_softmax_scale", "tau_form", "rope_on_rope_dims")
FLAWS = ("no_mscale", "no_tau", "rotate_half", "softmax_scoring",
         "value_from_rope_columns")
_QUERY_BLOCK = 2048


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


def describe(cfg: dict) -> dict:
    """The sizes the equations need, from the configuration file alone."""
    share = cfg.get("deployment_share", {})
    rp = cfg["rope_parameters"]
    return {
        "n_layers": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope_dim": int(cfg["qk_rope_head_dim"]),
        "v_dim": int(cfg["v_head_dim"]),
        "dense_first": int(cfg.get("first_k_dense_replace", 0)),
        "interleave": bool(cfg.get("rope_interleave", False)),
        "rope": tuple(sorted(rp.items())),
        "held": int(cfg["n_routed_experts"]),
        "first": int(share.get("first_expert", 0)),
        "top_k": int(cfg["num_experts_per_tok"]),
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


def apply_rope(x, rope: dict, interleave: bool):
    """``x`` (T, heads, D) at positions 0..T-1, every column turned."""
    D = x.shape[-1]
    f = jnp.asarray(inv_freq(rope, D))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * f[None, :]
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


def _query_block(t: int) -> int:
    """The largest divisor of ``t`` that is at most ``_QUERY_BLOCK``."""
    return max(b for b in range(1, min(t, _QUERY_BLOCK) + 1) if t % b == 0)


@functools.partial(jax.jit, static_argnames=("dense", "dims", "quant", "flaw"))
def layer(x, lp, dense: bool, dims, quant=_identity, flaw: str = ""):
    """One layer over the whole sequence ``x`` (T, d), float32."""
    m = dict(dims)
    t = x.shape[0]
    H, n, r, vd = m["heads"], m["nope"], m["rope_dim"], m["v_dim"]
    rank, eps = m["kv_rank"], m["eps"]
    rope = dict(m["rope"])
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    h = rmsnorm(x, _f32(lp["ln1"]), eps)
    c_q = rmsnorm(mm(h, lp["wq_a"]), _f32(lp["q_norm"]), eps)
    q = mm(c_q, lp["wq_b"]).reshape(t, H, n + r)
    kv = mm(h, lp["wkv_a"])
    c_kv = rmsnorm(kv[:, :rank], _f32(lp["kv_norm"]), eps)
    interleave = m["interleave"] and flaw != "rotate_half"
    q_pe = apply_rope(q[..., n:], rope, interleave)
    k_pe = apply_rope(kv[:, None, rank:], rope, interleave)[:, 0]   # (T, r)
    up = mm(c_kv, lp["wkv_b"]).reshape(t, H, n + vd)
    k_nope, v = up[..., :n], up[..., n:]
    if flaw == "value_from_rope_columns":
        # the value taken from the entry's LAST kv_rank columns, the rotary
        # ones among them, as a kernel that slices the page wrongly would
        shifted = jnp.concatenate([c_kv, k_pe], axis=-1)[:, -rank:]
        v = mm(shifted, lp["wkv_b"]).reshape(t, H, n + vd)[..., n:]
    scale = (n + r) ** -0.5
    if flaw != "no_mscale":
        scale *= yarn_m(rope, rope.get("mscale_all_dim", 0.0)) ** 2
    pos = jnp.arange(t)
    tau = jnp.ones((t,), jnp.float32)
    if flaw != "no_tau" and rope.get("llama_4_scaling_beta"):
        tau = 1.0 + rope["llama_4_scaling_beta"] * jnp.log1p(jnp.floor(
            pos / rope["original_max_position_embeddings"]))
    qb = _query_block(t)

    def one_head(n_):  # a block of queries' (qb, T) scores at a time
        qn = jax.lax.dynamic_index_in_dim(q[..., :n], n_, 1, keepdims=False)
        qp = jax.lax.dynamic_index_in_dim(q_pe, n_, 1, keepdims=False)
        kn = jax.lax.dynamic_index_in_dim(k_nope, n_, 1, keepdims=False)
        vn = jax.lax.dynamic_index_in_dim(v, n_, 1, keepdims=False)

        def block(b):
            rows = b * qb + jnp.arange(qb)
            s = (jnp.matmul(quant(qn[rows]), quant(kn).T)
                 + jnp.matmul(quant(qp[rows]), quant(k_pe).T))
            s = s * (scale * tau[rows])[:, None]
            p = jax.nn.softmax(
                jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf), axis=-1)
            return jnp.matmul(quant(p), quant(vn))

        return jax.lax.map(block, jnp.arange(t // qb)).reshape(t, vd)

    o = jax.lax.map(one_head, jnp.arange(H)).transpose(1, 0, 2)
    x = x + mm(o.reshape(t, H * vd), lp["wo"])
    h = rmsnorm(x, _f32(lp["ln2"]), eps)
    if dense:
        return x + mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
                      lp["w_down"])
    mp = lp["moe"]
    logits = jnp.matmul(h, _f32(mp["router"]))
    if flaw == "softmax_scoring":
        sc = jax.nn.softmax(logits, axis=-1)
    else:
        sc = jax.nn.sigmoid(logits)
    _, topi = jax.lax.top_k(sc + mp["e_bias"], m["top_k"])
    topv = jnp.take_along_axis(sc, topi, axis=-1)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    # (T, held): the weight each held expert has for each token, 0 if unpicked
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
    return x + m["scale"] * routed + shared


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, ln_f, w_head, positions, eps, quant=_identity):
    xs = rmsnorm(x[positions], _f32(ln_f), eps)
    return jnp.matmul(quant(xs), quant(_f32(w_head)).T)


def _dims(cfg: dict):
    return tuple(sorted(describe(cfg).items()))


def forward(params: dict, cfg: dict, tokens, quant=_identity, flaw: str = ""):
    """The residual stream after the last layer, (T, d) float32."""
    m = describe(cfg)
    dims = _dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["emb"], jnp.asarray(tokens), axis=0))
        for i in range(m["n_layers"]):
            x = layer(x, params[f"l{i}"], dense=i < m["dense_first"],
                      dims=dims, quant=quant, flaw=flaw)
    return x


def logits_at(params: dict, cfg: dict, tokens, positions, pad_to: int,
              quant=_identity, flaw: str = ""):
    """Float32 logits over the held vocabulary after the given ``positions``
    of ``tokens`` (1-D ints). The sequence is padded to ``pad_to`` so every
    call has one shape; the padding lies after every real position and is
    causally invisible."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    x = forward(params, cfg, toks, quant=quant, flaw=flaw)
    with jax.default_matmul_precision("highest"):
        return head(x, params["ln_f"], params["head"],
                    jnp.asarray(positions, jnp.int32),
                    eps=describe(cfg)["eps"], quant=quant)


def served_gaps(params: dict, cfg: dict, tokens, n_prompt: int, pad_to: int,
                max_out: int, control: bool = False, flaw: str = "") -> dict:
    """For one served request (``tokens`` = prompt + served tokens): at every
    served position, how far the served token's reference logit lies below
    the reference's best. With ``control``, the same for the token that the
    float8 control puts first at that position."""
    tokens = np.asarray(tokens, np.int64)
    n_out = len(tokens) - n_prompt
    pos = np.full(max_out, n_prompt - 1, np.int32)
    pos[:n_out] = np.arange(n_prompt - 1, len(tokens) - 1)
    ref = np.asarray(logits_at(params, cfg, tokens[:-1], pos, pad_to,
                               flaw=flaw))[:n_out]
    best = ref.max(axis=-1)
    served = tokens[n_prompt:]
    out = {"gaps": best - ref[np.arange(n_out), served],
           "argmax_agree": float((ref.argmax(-1) == served).mean())}
    if control:
        low = np.asarray(logits_at(params, cfg, tokens[:-1], pos, pad_to,
                                   quant=fp8_operand))[:n_out]
        out["control_gaps"] = best - ref[np.arange(n_out), low.argmax(-1)]
    return out
