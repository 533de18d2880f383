"""The plain reference of the ``serve_falconh1`` cells: the decoder that the
configuration file describes (Falcon-H1-34B-Instruct's published keys, the
``falcon_h1`` configuration family), written from the equations below in
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``;
no cache, no kernel, no batching, no chunked scan: the state-space
recurrence runs TOKEN BY TOKEN (``lax.scan`` over the sequence, the state
``(heads, head_dim, state)``), one full forward over a whole sequence.
Nothing of ``marlin_tpu`` is imported. The weights it is given are the
program's own (bfloat16; gains, ``A_log``, ``dt_bias``, ``D`` float32); they
are upcast a layer at a time, the head a block of rows at a time. Attention
runs a head and a block of queries at a time so that 5,120 positions fit
beside the weights.

``x`` is the residual stream; ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) *
g``. ``x = E[token] * embedding_multiplier``. One layer::

    u = rmsnorm(x, g1)                          both mixers read u
    attention (H query heads over K KV heads, head h reads KV head h // (H/K)):
      a_in = u * attention_in_multiplier
      q = a_in W_q ; k = (a_in W_k) * key_multiplier ; v = a_in W_v
      q, k <- rope at the token's position (whole head, rotate-half,
              inv_freq_i = rope_theta^(-2i/D))
      o_h = softmax_{j <= i}(q_h(i) . k(j) / sqrt(D)) v ;
      a = (concat_h(o_h) W_o) * attention_out_multiplier
    mixer (S heads of P channels, a state of N columns; G groups):
      p = ((u * ssm_in_multiplier) W_in) * mup        mup: ssm_multipliers[0..4]
          over the segments [z (S P) | x_s (S P) | B (G N) | C (G N) | dt (S)]
      [x_s | B | C] <- silu(conv(.) + b): causal, depthwise, mamba_d_conv taps
      dt = softplus(dt + dt_bias) ; A = -exp(A_log)
      per head s (group g = s // (S/G)), token by token:
        St = exp(dt A) S(t-1) + dt x_t B_t^T ;  y_t = St C_t + D x_t
      y = rmsnorm_grouped(y * silu(z), g_m)   (mamba_rms_norm, gate first:
          mamba_norm_before_gate false; the norm over each of the G groups of
          S P / G channels)
      m = (y W_out) * ssm_out_multiplier
    x = x + a + m
    g = rmsnorm(x, g2)
    f = ((g W_up) * silu((g W_gate) * mlp_multipliers[0])) W_down
    x = x + f * mlp_multipliers[1]

Head: ``logits = (rmsnorm(x, g_f) W_head^T) * lm_head_multiplier``.

``quant`` puts a lower precision in the reference's place (the control):
every matmul operand, and the recurrence's ``x``, ``B`` and ``C``, go
through it first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 1024
_HEAD_BLOCKS = 8


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
    """The sizes and multipliers the equations need, from the configuration
    file alone."""
    return {
        "n_layers": int(cfg["num_hidden_layers"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "theta": float(cfg["rope_theta"]),
        "eps": float(cfg["rms_norm_eps"]),
        "s_heads": int(cfg["mamba_n_heads"]),
        "s_dim": int(cfg["mamba_d_head"]),
        "s_state": int(cfg["mamba_d_state"]),
        "s_groups": int(cfg["mamba_n_groups"]),
        "s_conv": int(cfg["mamba_d_conv"]),
        "gate_first": not cfg.get("mamba_norm_before_gate", False),
        "rms_norm": bool(cfg.get("mamba_rms_norm", True)),
        "emb_mult": float(cfg["embedding_multiplier"]),
        "attn_in": float(cfg["attention_in_multiplier"]),
        "attn_out": float(cfg["attention_out_multiplier"]),
        "key_mult": float(cfg["key_multiplier"]),
        "ssm_in": float(cfg["ssm_in_multiplier"]),
        "ssm_out": float(cfg["ssm_out_multiplier"]),
        "mup": tuple(float(v) for v in cfg["ssm_multipliers"]),
        "mlp": tuple(float(v) for v in cfg["mlp_multipliers"]),
        "head_mult": float(cfg["lm_head_multiplier"])}


def apply_rope(x, theta: float):
    """``x`` (T, heads, D) at positions 0..T-1, every column turned, the
    rotate-half form (column ``i`` with ``i + D/2``)."""
    D = x.shape[-1]
    f = jnp.asarray((1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64)
                                     / D)).astype(np.float32))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * f[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(w):
    return w.astype(jnp.float32)


def _query_block(t: int) -> int:
    """The largest divisor of ``t`` that is at most ``_QUERY_BLOCK``."""
    return max(b for b in range(1, min(t, _QUERY_BLOCK) + 1) if t % b == 0)


def attention(u, lp, m: dict, quant):
    """The attention branch over the whole sequence ``u`` (T, d)."""
    t = u.shape[0]
    H, K, D = m["heads"], m["kv_heads"], m["head_dim"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    a_in = u * m["attn_in"]
    q = apply_rope(mm(a_in, lp["wq"]).reshape(t, H, D), m["theta"])
    k = apply_rope((mm(a_in, lp["wk"]) * m["key_mult"]).reshape(t, K, D),
                   m["theta"])
    v = mm(a_in, lp["wv"]).reshape(t, K, D)
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

    o = jax.lax.map(one_head, jnp.arange(H)).transpose(1, 0, 2)
    return mm(o.reshape(t, H * D), lp["wo"]) * m["attn_out"]


def mixer(u, sp, m: dict, quant):
    """The state-space branch over the whole sequence ``u`` (T, d): the
    recurrence token by token."""
    t = u.shape[0]
    S, P, N, G = m["s_heads"], m["s_dim"], m["s_state"], m["s_groups"]
    di, gn, taps = S * P, G * N, m["s_conv"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    mup = np.repeat(np.asarray(m["mup"], np.float32), (di, di, gn, gn, S))
    p = mm(u * m["ssm_in"], sp["w_in"]) * mup
    z, xbc, dt = p[:, :di], p[:, di:di + di + 2 * gn], p[:, 2 * di + 2 * gn:]
    # causal depthwise convolution: tap k meets the input taps - 1 - k back
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    w = _f32(sp["conv_w"])
    conv = _f32(sp["conv_b"])[None, :] + sum(
        padded[k:k + t] * w[k][None, :] for k in range(taps))
    act = jax.nn.silu(conv)
    xs = quant(act[:, :di]).reshape(t, S, P)
    Bm = quant(act[:, di:di + gn]).reshape(t, G, N)
    Cm = quant(act[:, di + gn:]).reshape(t, G, N)
    dt = jax.nn.softplus(dt + _f32(sp["dt_bias"]))
    A = -jnp.exp(_f32(sp["A_log"]))
    group = jnp.arange(S) // (S // G)

    def step(state, tok):  # state (S, P, N)
        x_t, b_t, c_t, dt_t = tok
        bh, ch = b_t[group], c_t[group]                           # (S, N)
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * bh[:, None, :])
        return state, jnp.sum(state * ch[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((S, P, N), jnp.float32),
                        (xs, Bm, Cm, dt))
    y = (y + _f32(sp["D"])[None, :, None] * xs).reshape(t, di)

    def norm(v):
        vg = v.reshape(t, G, di // G)
        vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True)
                                + m["eps"])
        return vg.reshape(t, di) * _f32(sp["norm"])

    gate = jax.nn.silu(z)
    if not m["rms_norm"]:
        y = y * gate
    elif m["gate_first"]:
        y = norm(y * gate)
    else:
        y = norm(y) * gate
    return mm(y, sp["w_out"]) * m["ssm_out"]


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def layer(x, lp, dims, quant=_identity):
    """One layer over the whole sequence ``x`` (T, d), float32."""
    m = dict(dims)
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    u = rmsnorm(x, _f32(lp["ln1"]), m["eps"])
    x = x + attention(u, lp, m, quant) + mixer(u, lp["ssm"], m, quant)
    g = rmsnorm(x, _f32(lp["ln2"]), m["eps"])
    gate_mult, down_mult = m["mlp"]
    f = mm(mm(g, lp["w_up"]) * jax.nn.silu(mm(g, lp["w_gate"]) * gate_mult),
           lp["w_down"])
    return x + f * down_mult


@functools.partial(jax.jit, static_argnames=("eps", "mult", "quant"))
def head(x, ln_f, w_head, positions, eps, mult, quant=_identity):
    """Logits after ``positions``, the head a block of rows at a time."""
    xs = quant(rmsnorm(x[positions], _f32(ln_f), eps))
    v = w_head.shape[0]
    nb = next(b for b in range(_HEAD_BLOCKS, 0, -1) if v % b == 0)
    blocks = w_head.reshape(nb, v // nb, w_head.shape[1])
    out = jax.lax.map(lambda wb: jnp.matmul(xs, quant(_f32(wb)).T), blocks)
    return out.transpose(1, 0, 2).reshape(xs.shape[0], v) * mult


def _dims(cfg: dict):
    return tuple(sorted(describe(cfg).items()))


def forward(params: dict, cfg: dict, tokens, quant=_identity):
    """The residual stream after the last layer, (T, d) float32."""
    m = describe(cfg)
    dims = _dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["emb"], jnp.asarray(tokens),
                          axis=0)) * m["emb_mult"]
        for i in range(m["n_layers"]):
            x = layer(x, params[f"l{i}"], dims=dims, quant=quant)
    return x


def logits_at(params: dict, cfg: dict, tokens, positions, pad_to: int,
              quant=_identity):
    """Float32 logits over the vocabulary after the given ``positions`` of
    ``tokens`` (1-D ints). The sequence is padded to ``pad_to`` so every call
    has one shape; the padding lies after every real position and is causally
    invisible (to the attention and to the recurrence alike)."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    x = forward(params, cfg, toks, quant=quant)
    m = describe(cfg)
    with jax.default_matmul_precision("highest"):
        return head(x, params["ln_f"], params["head"],
                    jnp.asarray(positions, jnp.int32), eps=m["eps"],
                    mult=m["head_mult"], quant=quant)


def served_gaps(params: dict, cfg: dict, tokens, n_prompt: int, pad_to: int,
                max_out: int, control: bool = False) -> dict:
    """For one served request (``tokens`` = prompt + served tokens): at every
    served position, how far the served token's reference logit lies below
    the reference's best. With ``control``, the same for the token that the
    float8 control puts first at that position."""
    tokens = np.asarray(tokens, np.int64)
    n_out = len(tokens) - n_prompt
    pos = np.full(max_out, n_prompt - 1, np.int32)
    pos[:n_out] = np.arange(n_prompt - 1, len(tokens) - 1)
    ref = np.asarray(logits_at(params, cfg, tokens[:-1], pos, pad_to))[:n_out]
    best = ref.max(axis=-1)
    served = tokens[n_prompt:]
    out = {"gaps": best - ref[np.arange(n_out), served],
           "argmax_agree": float((ref.argmax(-1) == served).mean())}
    if control:
        low = np.asarray(logits_at(params, cfg, tokens[:-1], pos, pad_to,
                                   quant=fp8_operand))[:n_out]
        out["control_gaps"] = best - ref[np.arange(n_out), low.argmax(-1)]
    return out
