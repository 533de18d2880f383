"""The plain reference of the ``serve_laguna`` cells: the decoder that the
configuration file describes (Laguna-S-2.1's published keys), written from
the equations below in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``; no cache, no kernel, no
batching, one full forward over a whole sequence. Nothing of ``marlin_tpu``
is imported. The weights it is given are the program's own (bfloat16, the 64
held experts, the held slice of the vocabulary); they are upcast a layer, or
an expert, at a time.

``x`` is the residual stream; ``h = rmsnorm(x, g) = x * rsqrt(mean(x^2) +
eps) * g``.

Attention, layer l, with ``H_l`` query heads, 8 KV heads, head size 128:
``q = h Wq``, ``k = h Wk``, ``v = h Wv``; ``q, k <- rope_l(q, k, position)``;
scores ``q k^T / sqrt(128)``, causal, and on a sliding layer key ``j`` is
visible to query ``i`` only if ``i - j < window``; query head ``n`` uses KV
head ``n // (H_l / 8)``; ``o = softmax(scores) v``; ``o_n <- sigmoid(h
Wgate)_n o_n``; ``x <- x + concat(o) Wo``.

rope, sliding layers: all 128 dimensions, ``inv_freq_i = theta^(-2i/128)``,
rotate-half form. rope, full layers: the first ``D = 64`` dimensions only,
YaRN: ``extra_i = theta^(-2i/D)``, ``inter_i = extra_i / factor``; ``c(r) =
D ln(original_max / (2 pi r)) / (2 ln theta)``, ``low = floor(c(beta_fast))``,
``high = ceil(c(beta_slow))`` clamped to ``[0, D-1]``; ``ramp_i = clip((i -
low) / (high - low), 0, 1)``; ``inv_freq_i = inter_i ramp_i + extra_i (1 -
ramp_i)``; cos and sin times ``attention_factor``.

FFN, dense layers: ``x <- x + (silu(h Wg) * (h Wu)) Wd``. FFN, expert layers:
``s = softmax(h Wr)`` over all the model's experts; ``I`` = the ``top_k``
largest; ``w_i = s_i / sum_{j in I} s_j``; ``E_i(h) = (silu(h Wg_i) * (h
Wu_i)) Wd_i``; ``x <- x + scale * sum_{i in I, i held here} w_i E_i(h) +
E_shared(h)``. What the absent experts would add is left out.

Head: ``logits = rmsnorm(x, g_f) W_head^T`` over the held rows.

ASSUMED (the configuration file gives each reason): the gate's form (sigmoid
of a linear map of the normed layer input, one scalar a head, on the
attention output before ``Wo``); softmax scoring and an ungated shared
expert; the scaling factor on the routed sum only; no QK-norm; the window
convention ``i - j < window``.

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

ASSUMED = ("gate_form", "softmax_scoring_ungated_shared_expert",
           "scaling_on_routed_sum_only", "no_qk_norm", "window_convention")
FLAWS = ("no_routed_scale", "no_gate", "window_plus_one", "rope_swapped",
         "dropped_pick")


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
    share = cfg.get("deployment_share", {})
    return {
        "n_layers": n, "head_dim": int(cfg["head_dim"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "kinds": tuple(cfg["layer_types"][:n]),
        "heads": tuple(int(h) for h in
                       cfg["num_attention_heads_per_layer"][:n]),
        "ffn": tuple(cfg["mlp_layer_types"][:n]),
        "window": int(cfg["sliding_window"]),
        "rope": tuple(sorted((k, tuple(sorted(v.items())))
                             for k, v in cfg["rope_parameters"].items())),
        "held": int(cfg["num_experts"]),
        "first": int(share.get("first_expert", 0)),
        "top_k": int(cfg["num_experts_per_tok"]),
        "scale": float(cfg["moe_routed_scaling_factor"]),
        "eps": float(cfg["rms_norm_eps"])}


def inv_freq(rope: dict, head_dim: int) -> np.ndarray:
    D = int(round(head_dim * float(rope.get("partial_rotary_factor", 1))))
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


def apply_rope(x, rope: dict):
    """``x`` (T, heads, head_dim) at positions 0..T-1."""
    f = jnp.asarray(inv_freq(rope, x.shape[-1]))
    D = 2 * f.shape[0]
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * f[None, :]
    factor = float(rope.get("attention_factor", 1.0))
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:D]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., D:]], axis=-1)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _f32(w):
    return w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("kind", "heads", "ffn", "dims",
                                             "quant", "flaw"))
def layer(x, lp, kind: str, heads: int, ffn: str, dims, quant=_identity,
          flaw: str = ""):
    """One layer over the whole sequence ``x`` (T, d), float32."""
    m = dict(dims)
    t = x.shape[0]
    dh, kvh, eps = m["head_dim"], m["kv_heads"], m["eps"]
    mm = lambda a, b: jnp.matmul(quant(a), quant(_f32(b)))  # noqa: E731
    rope_of = dict(m["rope"])
    rope_kind = kind
    if flaw == "rope_swapped":
        rope_kind = ("sliding_attention" if kind == "full_attention"
                     else "full_attention")
    rope = dict(rope_of[rope_kind])
    h = rmsnorm(x, _f32(lp["ln1"]), eps)
    q = apply_rope(mm(h, lp["wq"]).reshape(t, heads, dh), rope)
    k = apply_rope(mm(h, lp["wk"]).reshape(t, kvh, dh), rope)
    v = mm(h, lp["wv"]).reshape(t, kvh, dh)
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    seen = j <= i
    if kind == "sliding_attention":
        window = m["window"] + (1 if flaw == "window_plus_one" else 0)
        seen &= i - j < window
    group = heads // kvh

    def one_head(n):  # a (T, T) score matrix at a time
        qn = jax.lax.dynamic_index_in_dim(q, n, 1, keepdims=False)
        kn = jax.lax.dynamic_index_in_dim(k, n // group, 1, keepdims=False)
        vn = jax.lax.dynamic_index_in_dim(v, n // group, 1, keepdims=False)
        s = jnp.matmul(quant(qn), quant(kn).T) / math.sqrt(dh)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.matmul(quant(p), quant(vn))

    o = jax.lax.map(one_head, jnp.arange(heads)).transpose(1, 0, 2)
    if flaw != "no_gate":
        o = o * jax.nn.sigmoid(mm(h, lp["wgate"]))[:, :, None]
    x = x + mm(o.reshape(t, heads * dh), lp["wo"])
    h = rmsnorm(x, _f32(lp["ln2"]), eps)
    if ffn == "dense":
        return x + mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
                      lp["w_down"])
    mp = lp["moe"]
    s = jax.nn.softmax(jnp.matmul(h, _f32(mp["router"])), axis=-1)
    topv, topi = jax.lax.top_k(s, m["top_k"])
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    if flaw == "dropped_pick":  # the weakest pick of every token is lost
        topv = topv.at[:, -1].set(0.0)
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
    scale = 1.0 if flaw == "no_routed_scale" else m["scale"]
    return x + scale * routed + shared


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
            x = layer(x, params[f"l{i}"], kind=m["kinds"][i],
                      heads=m["heads"][i],
                      ffn="dense" if m["ffn"][i] == "dense" else "moe",
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
