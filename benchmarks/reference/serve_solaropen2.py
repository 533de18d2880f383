"""The plain reference of the ``serve_solaropen2`` cells: the decoder that the
configuration file describes (Solar-Open2-250B's published keys, the
``solar_open2`` configuration family), written from the equations below in
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``;
no cache, no kernel, no batching, no chunked form, no snapshot: the delta
rule runs TOKEN BY TOKEN (``lax.scan`` over the sequence, the state ``(heads,
key, value)``, the three lines below), every held expert is computed for
every token the plain way, one full forward over a whole sequence from an
empty state. Nothing of ``marlin_tpu`` is imported. The weights it is given
are the program's own (bfloat16; gains, ``A_log``, ``dt_bias``, the gate's
bias, the router and its selection bias float32); they are upcast a layer at
a time, the experts one at a time, the head a block of rows at a time.
Attention runs a head and a block of queries at a time so that 8,192
positions fit beside the weights.

``x`` is the residual stream (T, d), ``x = E[token]``; ``rmsnorm(x; g) = x *
rsqrt(mean(x^2) + eps) * g``; ``u = rmsnorm(x; g_1)``.

A KDA layer (H heads, keys and values of K; ``[W_q | W_k | W_v]`` is the one
matrix ``w_qkv``; the decay and the gate are low-rank, ``W_a2 (W_a1 u)`` and
``W_z2 (W_z1 u)``)::

    [q~ | k~ | v~] = silu(conv(u w_qkv))     causal, depthwise, 4 taps, no bias
    per head: q = q~ / sqrt(|q~|^2 + 1e-6) * K^-1/2 ; k = k~ / sqrt(|k~|^2 + 1e-6)
    g = -exp(A_log[h]) * softplus(W_a2 (W_a1 u) + dt_bias) ; a = exp(g)
                                              (H, K) a token: one a CHANNEL
    b = 2 * sigmoid(u W_b)     (the 2: kda_allow_neg_eigval)   one a head
    token by token, S (K x K) a head from zeros:
      S' = diag(a_t) S_{t-1}
      S_t = S' + b_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t
    y = (concat_h(rmsnorm_K(o_t; g_o) * sigmoid(W_z2 (W_z1 u) + b_z)_h)) W_o

A GQA layer (H heads of D over KV heads; NO positional term)::

    q = u W_q ; k = u W_k ; v = u W_v
    o_h = softmax_{j <= i}(q_h(i) . k(j) / sqrt(D)) v
    y = (concat_h(o_h) * sigmoid(u W_g)) W_o          the gate elementwise

Either: ``x = x + y``; ``h = rmsnorm(x; g_2)``; then the experts (the router
over all ``experts_total`` of the model; the experts ``[first_expert,
first_expert + E)`` are held and computed, what the others would add is left
out) and ONE shared expert::

    p = sigmoid(h W_r) ; picks = top-k of (p + bias)     the bias only selects
    w_e = p_e / sum over picks of p ; x routed_scaling_factor
    x = x + sum over held picks e of w_e * (silu(h G_e) * (h U_e)) D_e
          + (silu(h G_s) * (h U_s)) D_s

Head: ``logits = rmsnorm(x; g_f) W_head^T`` over the held rows.

``quant`` puts a lower precision in the reference's place (the control):
every matmul operand, and the recurrence's ``q``, ``k`` and ``v``, go
through it first (the router's stay float32, as the program's do). ``flaw``
leaves one piece of the description out or bends it (:data:`FLAWS`): what the
comparison must catch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 1024
_HEAD_BLOCKS = 8

#: ``scalar_decay``: a head's decays replaced by ONE (their mean in the
#: logarithm); ``step_not_doubled``: ``b = sigmoid`` in ``2 sigmoid``'s place;
#: ``rope_on_nope``: the GQA layer's queries and keys given a rotary
#: embedding (theta 10000, the config's unused ``rope_theta``);
#: ``no_gate``: the GQA layer's output gate dropped; ``no_shared_expert``;
#: ``bias_in_weights``: the picks' weights taken from ``p + bias``;
#: ``key_not_normalised``: ``k = k~`` as it leaves the SiLU
FLAWS = ("scalar_decay", "step_not_doubled", "rope_on_nope", "no_gate",
         "no_shared_expert", "bias_in_weights", "key_not_normalised")


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
    n = int(cfg["num_hidden_layers"])
    la = cfg["linear_attn_config"]
    gqa = set(int(i) for i in cfg["gqa_layers"])
    share = cfg.get("deployment_share", {})
    return {
        "kinds": tuple("gqa" if i in gqa else "kda" for i in range(n)),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "eps": float(cfg["rms_norm_eps"]),
        "theta": float(cfg.get("rope_theta", 10000.0)),
        "l_heads": int(la["num_heads"]),
        "l_dim": int(la["head_dim"]),
        "l_conv": int(la["short_conv_kernel_size"]),
        "l_step": 2.0 if cfg["kda_allow_neg_eigval"] else 1.0,
        "top_k": int(cfg["num_experts_per_tok"]),
        "first_expert": int(share.get("first_expert", 0)),
        "scale": float(cfg.get("routed_scaling_factor", 1.0))}


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(w):
    return w.astype(jnp.float32)


def _query_block(t: int) -> int:
    """The largest divisor of ``t`` that is at most ``_QUERY_BLOCK``."""
    return max(b for b in range(1, min(t, _QUERY_BLOCK) + 1) if t % b == 0)


def _rope(x, theta: float):
    """Only the ``rope_on_nope`` flaw turns anything: ``x`` (T, heads, D) at
    positions 0..T-1, dimension ``i`` with ``i + D/2``."""
    t, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(u, lp, m: dict, quant, flaw: str):
    """The GQA mixer over the whole sequence ``u`` (T, d): no positional
    term, the output gated elementwise before ``W_o``."""
    t = u.shape[0]
    H, K, D = m["heads"], m["kv_heads"], m["head_dim"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    q = mm(u, lp["wq"]).reshape(t, H, D)
    k = mm(u, lp["wk"]).reshape(t, K, D)
    if flaw == "rope_on_nope":
        q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    v = mm(u, lp["wv"]).reshape(t, K, D)
    pos = jnp.arange(t)
    qb = _query_block(t)

    def one_head(h):  # a block of queries' (qb, T) scores at a time
        qh = jax.lax.dynamic_index_in_dim(q, h, 1, keepdims=False)
        kh = jax.lax.dynamic_index_in_dim(k, h // (H // K), 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, h // (H // K), 1, keepdims=False)

        def block(b):
            rows = b * qb + jnp.arange(qb)
            s = jnp.matmul(quant(qh[rows]), quant(kh).T) * D ** -0.5
            p = jax.nn.softmax(
                jnp.where(pos[None, :] <= rows[:, None], s, -jnp.inf), axis=-1)
            return jnp.matmul(quant(p), quant(vh))

        return jax.lax.map(block, jnp.arange(t // qb)).reshape(t, D)

    o = jax.lax.map(one_head, jnp.arange(H)).transpose(1, 0, 2).reshape(
        t, H * D)
    if flaw != "no_gate":
        o = o * jax.nn.sigmoid(mm(u, lp["w_g"]))
    return mm(o, lp["wo"])


def kda_mixer(u, lp, m: dict, quant, flaw: str):
    """The KDA mixer over the whole sequence ``u`` (T, d): the delta rule
    with a decay a channel, token by token, from an empty state."""
    t = u.shape[0]
    H, K, taps = m["l_heads"], m["l_dim"], m["l_conv"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    qkv = mm(u, lp["w_qkv"])
    # causal depthwise convolution: tap j meets the input taps - 1 - j back
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    w = _f32(lp["conv_w"])
    act = jax.nn.silu(sum(padded[j:j + t] * w[j][None, :]
                          for j in range(taps)))

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q = quant(unit(act[:, :H * K].reshape(t, H, K)) * K ** -0.5)
    k = act[:, H * K:2 * H * K].reshape(t, H, K)
    k = quant(k if flaw == "key_not_normalised" else unit(k))
    v = quant(act[:, 2 * H * K:].reshape(t, H, K))
    g = -jnp.exp(_f32(lp["A_log"]))[None, :, None] * jax.nn.softplus(
        (mm(mm(u, lp["w_a1"]), lp["w_a2"]) + _f32(lp["dt_bias"]))
        .reshape(t, H, K))
    if flaw == "scalar_decay":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    a = jnp.exp(g)
    b = (1.0 if flaw == "step_not_doubled" else m["l_step"]) \
        * jax.nn.sigmoid(mm(u, lp["w_b"]))

    def step(S, tok):  # S (H, K, K): key channel x value channel
        q_t, k_t, v_t, a_t, b_t = tok
        S = a_t[:, :, None] * S
        S = S + (b_t[:, None] * k_t)[:, :, None] * (
            v_t - jnp.einsum("hkv,hk->hv", S, k_t))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, K, K), jnp.float32),
                        (q, k, v, a, b))
    o = rmsnorm(o, _f32(lp["o_norm"]), m["eps"])
    gate = jax.nn.sigmoid(mm(mm(u, lp["w_z1"]), lp["w_z2"])
                          + _f32(lp["b_z"])).reshape(t, H, K)
    return mm((o * gate).reshape(t, H * K), lp["wo"])


def experts(h, mp, m: dict, quant, flaw: str):
    """The expert layer over ``h`` (T, d): every held expert computed for
    every token, weighted by the router's picks (zero where not picked),
    and the shared expert."""
    t = h.shape[0]
    p = jax.nn.sigmoid(jnp.matmul(h, _f32(mp["router"])))
    biased = p + _f32(mp["e_bias"])
    _, picks = jax.lax.top_k(biased, m["top_k"])
    chosen = jnp.take_along_axis(biased if flaw == "bias_in_weights" else p,
                                 picks, axis=-1)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    # (T, experts of the model): a token's weight on each expert
    weight = jnp.zeros_like(p).at[jnp.arange(t)[:, None], picks].set(w)
    held = mp["e_gate"].shape[0]
    hq = quant(h)

    def swiglu(g, u, d):
        return jnp.matmul(quant(jax.nn.silu(jnp.matmul(hq, g))
                                * jnp.matmul(hq, u)), d)

    def one(e):
        g, u, d = (quant(_f32(jax.lax.dynamic_index_in_dim(
            mp[k], e, 0, keepdims=False))) for k in ("e_gate", "e_up",
                                                     "e_down"))
        return swiglu(g, u, d) * jax.lax.dynamic_index_in_dim(
            weight, m["first_expert"] + e, 1, keepdims=True)

    out = m["scale"] * jax.lax.fori_loop(
        0, held, lambda e, acc: acc + one(e), jnp.zeros_like(h))
    if flaw != "no_shared_expert":
        out = out + swiglu(*(quant(_f32(mp[k]))
                             for k in ("s_gate", "s_up", "s_down")))
    return out


@functools.partial(jax.jit, static_argnames=("kind", "dims", "quant", "flaw"))
def layer(x, lp, kind, dims, quant=_identity, flaw: str = ""):
    """One layer of ``kind`` over the whole sequence ``x`` (T, d), float32."""
    m = dict(dims)
    u = rmsnorm(x, _f32(lp["ln1"]), m["eps"])
    mixer = kda_mixer if kind == "kda" else attention
    x = x + mixer(u, lp, m, quant, flaw)
    h = rmsnorm(x, _f32(lp["ln2"]), m["eps"])
    return x + experts(h, lp["moe"], m, quant, flaw)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, ln_f, w_head, positions, eps, quant=_identity):
    """Logits after ``positions``, the head a block of rows at a time."""
    xs = quant(rmsnorm(x[positions], _f32(ln_f), eps))
    v = w_head.shape[0]
    nb = next(b for b in range(_HEAD_BLOCKS, 0, -1) if v % b == 0)
    blocks = w_head.reshape(nb, v // nb, w_head.shape[1])
    out = jax.lax.map(lambda wb: jnp.matmul(xs, quant(_f32(wb)).T), blocks)
    return out.transpose(1, 0, 2).reshape(xs.shape[0], v)


def _dims(cfg: dict):
    return tuple(sorted((k, v) for k, v in describe(cfg).items()
                        if k != "kinds"))


def forward(params: dict, cfg: dict, tokens, quant=_identity, flaw: str = ""):
    """The residual stream after the last layer, (T, d) float32."""
    kinds, dims = describe(cfg)["kinds"], _dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["emb"], jnp.asarray(tokens), axis=0))
        for i, kind in enumerate(kinds):
            x = layer(x, params[f"l{i}"], kind=kind, dims=dims, quant=quant,
                      flaw=flaw)
    return x


def logits_at(params: dict, cfg: dict, tokens, positions, pad_to: int,
              quant=_identity, flaw: str = ""):
    """Float32 logits over the held vocabulary after the given ``positions``
    of ``tokens`` (1-D ints). The sequence is padded to ``pad_to`` so every
    call has one shape; the padding lies after every real position and is
    causally invisible (to the attention and to the recurrence alike)."""
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
