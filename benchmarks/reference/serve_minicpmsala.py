"""The plain reference of the ``serve_minicpmsala`` cells: the decoder that the
configuration file describes (MiniCPM-SALA's published keys, the
``minicpm_sala`` configuration family, with the MiniCPM4 family's
``sparse_config``), written from the equations below in ``jax.numpy`` and
float32 with ``jax.default_matmul_precision("highest")``; no cache, no
kernel, no batching, no chunk, no compressed-key cache, no snapshot: the
lightning recurrence runs TOKEN BY TOKEN (``lax.scan`` over the sequence),
the block selection is computed for every query from the whole sequence's
keys by the five steps below, literally (a stable sort for the ranking), one
full forward over a whole sequence from an empty state. Nothing of
``marlin_tpu`` is imported. The weights it is given are the program's own
(bfloat16; gains float32); they are upcast a layer at a time, the head a
block of rows at a time. The decay is NOT read from the weights: it is
computed here from the published layer's index. Attention runs a KV head and
a block of queries at a time, the FFN a block of tokens at a time, so that
34,816 positions fit beside the weights.

``x`` is the residual stream; ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) *
g``. ``x = scale_emb * E[token]``. A layer: ``x = x + r * mixer(rmsnorm(x,
g1))``, then ``x = x + r * (silu(u W_gate) * (u W_up)) W_down`` with ``u =
rmsnorm(x, g2)`` and ``r = scale_depth / sqrt(mup_denominator)`` (the
PUBLISHED depth, whatever is held). Head: ``logits = rmsnorm(x, g_f) W_head^T
* dim_model_base / hidden_size``.

A ``lightning-attn`` layer (H heads of D, no grouping; ``u`` the normed
input)::

    q, k, v = u W_q, u W_k, u W_v ; per head q = rmsnorm_D(q, g_q), k = rmsnorm_D(k, g_k)
    q, k = rope(q), rope(k)      (theta, the whole head, half-rotation) ; q = q / sqrt(D)
    lambda_h = exp(-2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5))    l the published layer, L = 32
    token by token, S (D x D) a head from zeros:  S_t = lambda_h S_{t-1} + k_t v_t^T ; o_t = S_t^T q_t
    y = (sigmoid(u W_g) * rmsnorm_{H D}(concat_h o, g_o)) W_o

A ``minicpm4`` layer (H query heads over KV heads of D, group G = H / KV; no
rotary embedding)::

    q, k, v projected ; per head q = rmsnorm_D(q, g_q), k = rmsnorm_D(k, g_k)
    a query at position t < dense_len: causal softmax attention over all keys, scale 1 / sqrt(D)
    a query at t >= dense_len, per KV head g:
      1. c_j = mean(k_{s j}, ..., k_{s j + w - 1}), for every j with s j + w - 1 <= t   (w = kernel_size, s = kernel_stride)
      2. p_h = softmax_j(q_h . c_j / sqrt(D)) for each head h of the group ; s_j = sum_h p_{h, j}
      3. block b (tokens B b .. B b + B - 1) scores max(s_j : the window j touches the block and is complete)
      4. taken whatever they score: blocks 0 .. init_blocks - 1 and the query's own block with the
         window_size / B - 1 before it; the rest of the topk places go to the highest-scoring blocks
         between them (ties to the lower index); all blocks where fewer than topk exist
      5. causal softmax attention, scale 1 / sqrt(D), of the group's heads over the tokens <= t of the taken blocks
    y = (sigmoid(u W_g) * concat_h o) W_o

``quant`` puts a lower precision in the reference's place (the control):
every matmul operand, the recurrence's ``q``, ``k`` and ``v`` and the
compressed keys go through it first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_QUERY_BLOCK = 256
_TOKEN_BLOCK = 4096
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
    n = int(cfg["num_hidden_layers"])
    sc = cfg["sparse_config"]
    return {
        "kinds": tuple(cfg["mixer_types"][:n]),
        "first_layer": int(cfg.get("first_layer", 0)),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "eps": float(cfg["rms_norm_eps"]),
        "l_heads": int(cfg["lightning_nh"]),
        "theta": float(cfg["rope_theta"]),
        "depth": int(cfg["mup_denominator"]),
        "scale_emb": float(cfg["scale_emb"]),
        "r": float(cfg["scale_depth"]) / math.sqrt(cfg["mup_denominator"]),
        "logit_scale": float(cfg["dim_model_base"]) / cfg["hidden_size"],
        "kernel": int(sc["kernel_size"]), "stride": int(sc["kernel_stride"]),
        "block": int(sc["block_size"]), "topk": int(sc["topk"]),
        "init_blocks": int(sc["init_blocks"]),
        "window_blocks": int(sc["window_size"]) // int(sc["block_size"]),
        "dense_len": int(sc["dense_len"])}


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(w):
    return w.astype(jnp.float32)


def _divisor(t: int, cap: int) -> int:
    """The largest divisor of ``t`` that is at most ``cap``."""
    return max(b for b in range(1, min(t, cap) + 1) if t % b == 0)


def rope(x, theta: float):
    """Half-rotation over the whole head: ``x`` (T, heads, D) at positions
    0..T-1; dimension ``i`` turns with ``i + D/2``."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def decay(heads: int, layer: int, depth: int):
    """``lambda_h`` of published layer ``layer`` of ``depth``, (heads,)."""
    h = np.arange(1, heads + 1, dtype=np.float64)
    slope = 2.0 ** (-8.0 * h / heads) * (1.0 - layer / (depth - 1) + 1e-5)
    return jnp.asarray(np.exp(-slope), jnp.float32)


def lightning_mixer(u, lp, m: dict, lam, quant):
    """The lightning mixer over the whole normed sequence ``u`` (T, d) with
    the decays ``lam`` (heads,): the recurrence token by token, from an
    empty state."""
    t = u.shape[0]
    H, D = m["l_heads"], m["head_dim"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    q = rope(rmsnorm(mm(u, lp["wq"]).reshape(t, H, D), _f32(lp["q_norm"]),
                     m["eps"]), m["theta"]) / math.sqrt(D)
    k = rope(rmsnorm(mm(u, lp["wk"]).reshape(t, H, D), _f32(lp["k_norm"]),
                     m["eps"]), m["theta"])
    v = mm(u, lp["wv"]).reshape(t, H, D)
    lam = lam[:, None, None]

    def step(S, tok):  # S (H, D, D)
        q_t, k_t, v_t = tok
        S = lam * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), jnp.float32),
                        (quant(q), quant(k), quant(v)))
    o = rmsnorm(o.reshape(t, H * D), _f32(lp["o_norm"]), m["eps"])
    return mm(o * jax.nn.sigmoid(mm(u, lp["w_g"])), lp["wo"])


def taken_blocks(qg, c, rows, m: dict):
    """Steps 2-4 for a block of queries of one KV head's group: ``qg`` (G,
    Q, D) the group's queries at positions ``rows`` (Q,), ``c`` (J, D) the
    head's compressed keys (window ``j`` is tokens ``s j .. s j + w - 1``).
    Returns (Q, NB) bool over the ``NB = ceil(T / B)`` blocks."""
    w, s, B = m["kernel"], m["stride"], m["block"]
    J = c.shape[0]
    nb = m["n_blocks"]
    j = jnp.arange(J)
    complete = s * j[None, :] + w - 1 <= rows[:, None]              # (Q, J)
    sc = jnp.einsum("gqd,jd->gqj", qg, c) / math.sqrt(qg.shape[-1])
    p = jax.nn.softmax(jnp.where(complete[None], sc, -jnp.inf), axis=-1)
    sj = jnp.where(complete, jnp.nan_to_num(p).sum(axis=0), -jnp.inf)
    # step 3: the windows that touch block b: s j + w - 1 >= B b and s j <= B
    # b + B - 1; at most (B + w - 2) // s + 1 consecutive ones from the first
    b = jnp.arange(nb)
    cand = (-((w - 1 - B * b) // s))[:, None] + jnp.arange(
        (B + w - 2) // s + 1)[None, :]                          # (NB, n)
    touches = (cand >= 0) & (cand < J) & (s * cand <= B * b[:, None] + B - 1)
    score = jnp.max(jnp.where(touches[None], sj[:, jnp.clip(cand, 0, J - 1)],
                              -jnp.inf), axis=-1)                   # (Q, NB)
    # step 4
    own = rows // B
    exists = b[None, :] <= own[:, None]
    forced = exists & ((b[None, :] < m["init_blocks"])
                       | (b[None, :] > own[:, None] - m["window_blocks"]))
    free = m["topk"] - forced.sum(axis=-1)                            # (Q,)
    between = exists & ~forced
    ranked = jnp.where(between, score, -jnp.inf)
    order = jnp.argsort(-ranked, axis=-1, stable=True)  # ties: lower index
    rank = jnp.argsort(order, axis=-1)
    chosen = between & (rank < free[:, None])
    sparse = forced | chosen
    return jnp.where((rows < m["dense_len"])[:, None], exists, sparse)


def sparse_attention(u, lp, m: dict, quant):
    """The ``minicpm4`` mixer over the whole normed sequence ``u`` (T, d)."""
    t = u.shape[0]
    H, K, D = m["heads"], m["kv_heads"], m["head_dim"]
    G, w, s, B = H // K, m["kernel"], m["stride"], m["block"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    q = rmsnorm(mm(u, lp["wq"]).reshape(t, H, D), _f32(lp["q_norm"]), m["eps"])
    k = rmsnorm(mm(u, lp["wk"]).reshape(t, K, D), _f32(lp["k_norm"]), m["eps"])
    v = mm(u, lp["wv"]).reshape(t, K, D)
    # step 1: every window that lies inside the sequence
    J = max((t - w) // s + 1, 0)
    starts = s * jnp.arange(J)
    c = jnp.mean(k[starts[:, None] + jnp.arange(w)[None, :]], axis=1)  # (J, K, D)
    pos = jnp.arange(t)
    qb = _divisor(t, _QUERY_BLOCK)
    m = dict(m, n_blocks=-(-t // B))

    def one_head(g):  # a KV head's group, a block of queries at a time
        qg = jax.lax.dynamic_slice_in_dim(q, g * G, G, 1).transpose(1, 0, 2)
        kh = jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False)
        vh = jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False)
        ch = jax.lax.dynamic_index_in_dim(c, g, 1, keepdims=False)

        def block(i):
            rows = i * qb + jnp.arange(qb)
            qr = jax.lax.dynamic_slice_in_dim(qg, i * qb, qb, 1)  # (G, qb, D)
            took = taken_blocks(quant(qr), quant(ch), rows, m)    # (qb, NB)
            seen = took[:, pos // B] & (pos[None, :] <= rows[:, None])
            sc = jnp.matmul(quant(qr), quant(kh).T) / math.sqrt(D)
            p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
            return jnp.matmul(quant(p), quant(vh))                # (G, qb, D)

        o = jax.lax.map(block, jnp.arange(t // qb))          # (nqb, G, qb, D)
        return o.transpose(1, 0, 2, 3).reshape(G, t, D)

    o = jax.lax.map(one_head, jnp.arange(K))                     # (K, G, t, D)
    o = o.reshape(H, t, D).transpose(1, 0, 2).reshape(t, H * D)
    return mm(o * jax.nn.sigmoid(mm(u, lp["w_g"])), lp["wo"])


@functools.partial(jax.jit, static_argnames=("kind", "dims", "quant"))
def layer(x, lp, lam, kind, dims, quant=_identity):
    """One layer of ``kind`` over the whole sequence ``x`` (T, d), float32;
    ``lam`` (heads,) the decays of a lightning layer (a traced argument: one
    program serves every lightning layer)."""
    m = dict(dims)
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    u = rmsnorm(x, _f32(lp["ln1"]), m["eps"])
    if kind == "lightning-attn":
        y = lightning_mixer(u, lp, m, lam, quant)
    else:
        y = sparse_attention(u, lp, m, quant)
    x = x + m["r"] * y
    tb = _divisor(x.shape[0], _TOKEN_BLOCK)

    def ffn(xb):  # a block of tokens: 16384 columns of 34,816 rows are 2.3 GB
        ub = rmsnorm(xb, _f32(lp["ln2"]), m["eps"])
        return mm(jax.nn.silu(mm(ub, lp["w_gate"])) * mm(ub, lp["w_up"]),
                  lp["w_down"])

    f = jax.lax.map(ffn, x.reshape(-1, tb, x.shape[1])).reshape(x.shape)
    return x + m["r"] * f


@functools.partial(jax.jit, static_argnames=("eps", "scale", "quant"))
def head(x, ln_f, w_head, positions, eps, scale, quant=_identity):
    """Logits after ``positions``, the head a block of rows at a time."""
    xs = quant(rmsnorm(x[positions], _f32(ln_f), eps))
    v = w_head.shape[0]
    nb = next(b for b in range(_HEAD_BLOCKS, 0, -1) if v % b == 0)
    blocks = w_head.reshape(nb, v // nb, w_head.shape[1])
    out = jax.lax.map(lambda wb: jnp.matmul(xs, quant(_f32(wb)).T), blocks)
    return out.transpose(1, 0, 2).reshape(xs.shape[0], v) * scale


def _dims(cfg: dict):
    return tuple(sorted((k, v) for k, v in describe(cfg).items()
                        if k not in ("kinds", "first_layer")))


def forward(params: dict, cfg: dict, tokens, quant=_identity):
    """The residual stream after the last layer, (T, d) float32."""
    d = describe(cfg)
    dims = _dims(cfg)
    with jax.default_matmul_precision("highest"):
        x = d["scale_emb"] * _f32(jnp.take(params["emb"], jnp.asarray(tokens),
                                           axis=0))
        for i, kind in enumerate(d["kinds"]):
            x = layer(x, params[f"l{i}"],
                      decay(d["l_heads"], d["first_layer"] + i, d["depth"]),
                      kind=kind, dims=dims, quant=quant)
    return x


def logits_at(params: dict, cfg: dict, tokens, positions, pad_to: int,
              quant=_identity):
    """Float32 logits over the vocabulary after the given ``positions`` of
    ``tokens`` (1-D ints). The sequence is padded to ``pad_to`` so every call
    has one shape; the padding lies after every real position and is causally
    invisible (to the attention, to the selection and to the recurrence
    alike)."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    x = forward(params, cfg, toks, quant=quant)
    d = describe(cfg)
    with jax.default_matmul_precision("highest"):
        return head(x, params["ln_f"], params["head"],
                    jnp.asarray(positions, jnp.int32), eps=d["eps"],
                    scale=d["logit_scale"], quant=quant)


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
