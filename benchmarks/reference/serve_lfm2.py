"""The plain reference of the ``serve_lfm2`` cells: the decoder that the
configuration file describes (LFM2-8B-A1B's published keys, the ``lfm2_moe``
configuration family), written from the equations below in ``jax.numpy`` and
float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, no chunk, no tail, no snapshot: the short convolution
pads two zeros in front of the sequence and sums three shifted copies, every
held expert is computed for every token the plain way, the head is the
embedding itself. One full forward over a whole sequence. Nothing of
``marlin_tpu`` is imported. The weights it is given are the program's own
(bfloat16; gains, the router and its selection bias float32); they are
upcast a layer at a time, the experts one at a time, the tied head a block
of rows at a time. Attention runs a head and a block of queries at a time so
that 6,144 positions fit beside the weights.

``x`` is the residual stream (T, d), ``x = E[token]``; ``rmsnorm(x; g) = x *
rsqrt(mean(x^2) + eps) * g``; ``u = rmsnorm(x; g_op)``.

A ``conv`` layer (``W_in`` (d, 3d), its thirds ``b``, ``c``, ``z`` in that
order; the taps ``w`` (3, d), ``w_2`` on the current token)::

    [b | c | z] = u W_in ; s_t = b_t * z_t
    r_t = w_0 * s_{t-2} + w_1 * s_{t-1} + w_2 * s_t        (s before 0 is 0)
    y_t = (c_t * r_t) W_out

A ``full_attention`` layer (H heads of D over K KV heads, groups of H / K)::

    q = u W_q ; k = u W_k ; v = u W_v
    q_h <- rmsnorm_D(q_h; g_q) ; k_h <- rmsnorm_D(k_h; g_k)    (one gain, all heads)
    RoPE(theta) over the whole head, half-rotation (dimension i with i + D/2)
    o_h = softmax_{j <= i}(q_h(i) . k(j) / sqrt(D)) v ; y = concat_h(o_h) W_o

Either: ``x = x + y``; ``h = rmsnorm(x; g_ffn)``. Layers ``0 ..
num_dense_layers - 1``: ``x = x + (silu(h W_1) * (h W_3)) W_2``. The others
(E experts, the router over all ``experts_total`` of the model; the experts
``[first_expert, first_expert + E)`` are held and computed, what the others
would add is left out)::

    p = sigmoid(h W_r) ; picks = top-k of (p + bias)
    w_e = p_e / (sum over picks of p + 1e-6) ; x routed_scaling_factor
    x = x + sum over held picks e of w_e * (silu(h G_e) * (h U_e)) D_e

Head: ``logits = rmsnorm(x; g_emb) E^T`` (tied).

``quant`` puts a lower precision in the reference's place (the control):
every matmul operand goes through it first (the router's stay float32, as
the program's do).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 1024
_HEAD_BLOCKS = 8
_RENORM_EPS = 1e-6


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
    heads = int(cfg["num_attention_heads"])
    n = int(cfg["num_hidden_layers"])
    theta = cfg.get("rope_theta") or cfg["rope_parameters"]["rope_theta"]
    return {
        "kinds": tuple(cfg["layer_types"][:n]),
        "dense": int(cfg["num_dense_layers"]),
        "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg.get("head_dim")
                        or int(cfg["hidden_size"]) // heads),
        "theta": float(theta),
        "eps": float(cfg["norm_eps"]),
        "taps": int(cfg["conv_L_cache"]),
        "top_k": int(cfg["num_experts_per_tok"]),
        "first_expert": int(cfg.get("first_expert", 0)),
        "scale": float(cfg.get("routed_scaling_factor", 1.0))}


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(w):
    return w.astype(jnp.float32)


def _query_block(t: int) -> int:
    """The largest divisor of ``t`` that is at most ``_QUERY_BLOCK``."""
    return max(b for b in range(1, min(t, _QUERY_BLOCK) + 1) if t % b == 0)


def rope(x, theta: float):
    """``x`` (T, heads, D) at positions 0..T-1: dimension ``i`` turns with
    ``i + D/2`` by the angle ``position * theta^(-2i/D)``."""
    t, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def short_conv(u, lp, m: dict, quant):
    """The gated short convolution over the whole sequence ``u`` (T, d)."""
    t, d = u.shape
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    p = mm(u, lp["w_in"])
    b, c, z = p[:, :d], p[:, d:2 * d], p[:, 2 * d:]
    taps = m["taps"]
    s = jnp.concatenate([jnp.zeros((taps - 1, d)), b * z])
    w = _f32(lp["conv_w"])
    r = sum(s[j:j + t] * w[j][None, :] for j in range(taps))
    return mm(c * r, lp["w_out"])


def attention(u, lp, m: dict, quant):
    """The full-attention mixer over the whole sequence ``u`` (T, d)."""
    t = u.shape[0]
    H, K, D = m["heads"], m["kv_heads"], m["head_dim"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    q = rope(rmsnorm(mm(u, lp["wq"]).reshape(t, H, D), _f32(lp["q_norm"]),
                     m["eps"]), m["theta"])
    k = rope(rmsnorm(mm(u, lp["wk"]).reshape(t, K, D), _f32(lp["k_norm"]),
                     m["eps"]), m["theta"])
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

    o = jax.lax.map(one_head, jnp.arange(H)).transpose(1, 0, 2)
    return mm(o.reshape(t, H * D), lp["wo"])


def experts(h, mp, m: dict, quant):
    """The expert layer over ``h`` (T, d): every held expert computed for
    every token, weighted by the router's picks (zero where not picked)."""
    t = h.shape[0]
    p = jax.nn.sigmoid(jnp.matmul(h, _f32(mp["router"])))
    _, picks = jax.lax.top_k(p + _f32(mp["e_bias"]), m["top_k"])
    chosen = jnp.take_along_axis(p, picks, axis=-1)
    w = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + _RENORM_EPS)
    # (T, experts of the model): a token's weight on each expert
    weight = jnp.zeros_like(p).at[jnp.arange(t)[:, None], picks].set(w)
    held = mp["e_gate"].shape[0]
    hq = quant(h)

    def one(e):
        g, u, d = (quant(_f32(jax.lax.dynamic_index_in_dim(
            mp[k], e, 0, keepdims=False))) for k in ("e_gate", "e_up",
                                                     "e_down"))
        y = jnp.matmul(quant(jax.nn.silu(jnp.matmul(hq, g))
                             * jnp.matmul(hq, u)), d)
        return y * jax.lax.dynamic_index_in_dim(
            weight, m["first_expert"] + e, 1, keepdims=True)

    out = jax.lax.fori_loop(0, held, lambda e, acc: acc + one(e),
                            jnp.zeros_like(h))
    return m["scale"] * out


@functools.partial(jax.jit, static_argnames=("kind", "dense", "dims",
                                             "quant"))
def layer(x, lp, kind, dense, dims, quant=_identity):
    """One layer of ``kind`` over the whole sequence ``x`` (T, d), float32;
    ``dense``: a dense FFN (one of the leading layers), else the experts."""
    m = dict(dims)
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    u = rmsnorm(x, _f32(lp["ln1"]), m["eps"])
    mixer = short_conv if kind == "conv" else attention
    x = x + mixer(u, lp, m, quant)
    h = rmsnorm(x, _f32(lp["ln2"]), m["eps"])
    if dense:
        return x + mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
                      lp["w_down"])
    return x + experts(h, lp["moe"], m, quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, ln_f, emb, positions, eps, quant=_identity):
    """Logits after ``positions`` over the TIED head, the embedding a block
    of rows at a time."""
    xs = quant(rmsnorm(x[positions], _f32(ln_f), eps))
    v = emb.shape[0]
    nb = next(b for b in range(_HEAD_BLOCKS, 0, -1) if v % b == 0)
    blocks = emb.reshape(nb, v // nb, emb.shape[1])
    out = jax.lax.map(lambda wb: jnp.matmul(xs, quant(_f32(wb)).T), blocks)
    return out.transpose(1, 0, 2).reshape(xs.shape[0], v)


def _dims(cfg: dict):
    return tuple(sorted((k, v) for k, v in describe(cfg).items()
                        if k not in ("kinds", "dense")))


def forward(params: dict, cfg: dict, tokens, quant=_identity):
    """The residual stream after the last layer, (T, d) float32."""
    m, dims = describe(cfg), _dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["emb"], jnp.asarray(tokens), axis=0))
        for i, kind in enumerate(m["kinds"]):
            x = layer(x, params[f"l{i}"], kind=kind, dense=i < m["dense"],
                      dims=dims, quant=quant)
    return x


def logits_at(params: dict, cfg: dict, tokens, positions, pad_to: int,
              quant=_identity):
    """Float32 logits over the vocabulary after the given ``positions`` of
    ``tokens`` (1-D ints). The sequence is padded to ``pad_to`` so every call
    has one shape; the padding lies after every real position and is causally
    invisible (to the attention and to the convolution alike)."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    x = forward(params, cfg, toks, quant=quant)
    with jax.default_matmul_precision("highest"):
        return head(x, params["ln_f"], params["emb"],
                    jnp.asarray(positions, jnp.int32),
                    eps=describe(cfg)["eps"], quant=quant)


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
