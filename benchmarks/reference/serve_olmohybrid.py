"""The plain reference of the ``serve_olmohybrid`` cells: the decoder that the
configuration file describes (Olmo-Hybrid-7B's published keys, the
``olmo_hybrid`` configuration family), written from the equations below in
``jax.numpy`` and float32 with ``jax.default_matmul_precision("highest")``;
no cache, no kernel, no batching, no chunked form, no snapshot: the gated
delta rule runs TOKEN BY TOKEN (``lax.scan`` over the sequence, the state
``(heads, key_dim, value_dim)``, the three lines below), one full forward
over a whole sequence from an empty state. Nothing of ``marlin_tpu`` is
imported. The weights it is given are the program's own (bfloat16; gains,
``A_log``, ``dt_bias`` float32); they are upcast a layer at a time, the head
a block of rows at a time. Attention runs a head and a block of queries at a
time so that 3,840 positions fit beside the weights.

``x`` is the residual stream; ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) *
g``. ``x = E[token]``. A ``linear_attention`` layer (H heads, keys of K
values, values of V; ``[W_q | W_k | W_v]`` is the one matrix ``w_qkv``, ``[W_a
| W_b]`` the one matrix ``w_ab``)::

    [q~ | k~ | v~] = silu(conv(x w_qkv))     causal, depthwise, 4 taps, no bias
    per head: q = q~ / sqrt(|q~|^2 + 1e-6) * K^-1/2 ; k = k~ / sqrt(|k~|^2 + 1e-6)
    g = -exp(A_log) * softplus(x W_a + dt_bias) ; a = exp(g)       one a head
    b = 2 * sigmoid(x W_b)     (the 2: linear_allow_neg_eigval)    one a head
    token by token, S (K x V) a head from zeros:
      S' = a_t S_{t-1}
      S_t = S' + b_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t
    y = (concat_h(rmsnorm_V(o_t, g_o) * silu(x W_g)_h)) W_o

A ``full_attention`` layer (H heads of D over KV heads, no rotary embedding)::

    q = rmsnorm(x W_q, g_q) ; k = rmsnorm(x W_k, g_k)   (over the whole width)
    v = x W_v ; o_h = softmax_{j <= i}(q_h(i) . k(j) / sqrt(D)) v
    y = concat_h(o_h) W_o

The block, either kind: ``x = x + rmsnorm(y, g1)``; ``x = x + rmsnorm((x W_up
* silu(x W_gate)) W_down, g2)``. Head: ``logits = rmsnorm(x, g_f) W_head^T``.

``quant`` puts a lower precision in the reference's place (the control):
every matmul operand, and the recurrence's ``q``, ``k`` and ``v``, go
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
    """The sizes the equations need, from the configuration file alone."""
    heads = int(cfg["num_attention_heads"])
    n = int(cfg["num_hidden_layers"])
    return {
        "kinds": tuple(cfg["layer_types"][:n]),
        "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg.get("head_dim")
                        or int(cfg["hidden_size"]) // heads),
        "eps": float(cfg["rms_norm_eps"]),
        "l_heads": int(cfg["linear_num_key_heads"]),
        "l_key": int(cfg["linear_key_head_dim"]),
        "l_value": int(cfg["linear_value_head_dim"]),
        "l_conv": int(cfg["linear_conv_kernel_dim"]),
        "l_step": 2.0 if cfg.get("linear_allow_neg_eigval") else 1.0}


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(w):
    return w.astype(jnp.float32)


def _query_block(t: int) -> int:
    """The largest divisor of ``t`` that is at most ``_QUERY_BLOCK``."""
    return max(b for b in range(1, min(t, _QUERY_BLOCK) + 1) if t % b == 0)


def attention(x, lp, m: dict, quant):
    """The full-attention mixer over the whole sequence ``x`` (T, d)."""
    t = x.shape[0]
    H, K, D = m["heads"], m["kv_heads"], m["head_dim"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    q = rmsnorm(mm(x, lp["wq"]), _f32(lp["q_norm"]), m["eps"]).reshape(t, H, D)
    k = rmsnorm(mm(x, lp["wk"]), _f32(lp["k_norm"]), m["eps"]).reshape(t, K, D)
    v = mm(x, lp["wv"]).reshape(t, K, D)
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


def delta_mixer(x, lp, m: dict, quant):
    """The linear-attention mixer over the whole sequence ``x`` (T, d): the
    gated delta rule token by token, from an empty state."""
    t = x.shape[0]
    H, K, V, taps = m["l_heads"], m["l_key"], m["l_value"], m["l_conv"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    qkv = mm(x, lp["w_qkv"])
    # causal depthwise convolution: tap k meets the input taps - 1 - k back
    padded = jnp.concatenate([jnp.zeros((taps - 1, qkv.shape[1])), qkv])
    w = _f32(lp["conv_w"])
    act = jax.nn.silu(sum(padded[k:k + t] * w[k][None, :]
                          for k in range(taps)))

    def unit(v):
        return v / jnp.sqrt(jnp.sum(v * v, axis=-1, keepdims=True) + 1e-6)

    q = quant(unit(act[:, :H * K].reshape(t, H, K)) * K ** -0.5)
    k = quant(unit(act[:, H * K:2 * H * K].reshape(t, H, K)))
    v = quant(act[:, 2 * H * K:].reshape(t, H, V))
    ab = mm(x, lp["w_ab"])
    a = jnp.exp(-jnp.exp(_f32(lp["A_log"]))
                * jax.nn.softplus(ab[:, :H] + _f32(lp["dt_bias"])))
    b = m["l_step"] * jax.nn.sigmoid(ab[:, H:])

    def step(S, tok):  # S (H, K, V)
        q_t, k_t, v_t, a_t, b_t = tok
        S = a_t[:, None, None] * S
        S = S + (b_t[:, None] * k_t)[:, :, None] * (
            v_t - jnp.einsum("hkv,hk->hv", S, k_t))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, K, V), jnp.float32),
                        (q, k, v, a, b))
    o = rmsnorm(o, _f32(lp["o_norm"]), m["eps"])
    gate = jax.nn.silu(mm(x, lp["w_g"])).reshape(t, H, V)
    return mm((o * gate).reshape(t, H * V), lp["wo"])


@functools.partial(jax.jit, static_argnames=("kind", "dims", "quant"))
def layer(x, lp, kind, dims, quant=_identity):
    """One layer of ``kind`` over the whole sequence ``x`` (T, d), float32."""
    m = dict(dims)
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    mixer = delta_mixer if kind == "linear_attention" else attention
    x = x + rmsnorm(mixer(x, lp, m, quant), _f32(lp["ln1"]), m["eps"])
    f = mm(mm(x, lp["w_up"]) * jax.nn.silu(mm(x, lp["w_gate"])), lp["w_down"])
    return x + rmsnorm(f, _f32(lp["ln2"]), m["eps"])


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


def forward(params: dict, cfg: dict, tokens, quant=_identity):
    """The residual stream after the last layer, (T, d) float32."""
    kinds, dims = describe(cfg)["kinds"], _dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["emb"], jnp.asarray(tokens), axis=0))
        for i, kind in enumerate(kinds):
            x = layer(x, params[f"l{i}"], kind=kind, dims=dims, quant=quant)
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
    with jax.default_matmul_precision("highest"):
        return head(x, params["ln_f"], params["head"],
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
