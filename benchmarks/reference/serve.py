"""The plain reference of the serving cells: the decoder block as the
configuration's ``block`` describes it, in ``jax.numpy`` and float32 with
``jax.default_matmul_precision("highest")``; no cache, no kernel, no batching.
Written from the description; nothing of ``marlin_tpu`` is imported.

    x      = emb[tokens]
    per layer:
      h    = rmsnorm(x, ln1)
      q,k,v= h wq, h wk, h wv           split into heads of d/heads
      s    = q k^T / sqrt(d/heads)      causal
      x    = x + softmax(s) v wo
      x    = x + gelu_tanh(rmsnorm(x, ln2) w1) w2
    logits = rmsnorm(x, ln_f) emb^T

``quant`` puts a lower precision in the reference's place (the control): every
matmul operand goes through it first.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def rmsnorm(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * g


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)))


def fp8_operand(x):
    """Per-tensor scaled float8 (e4m3: 3 mantissa bits, least normal exponent
    -6, largest value 448) and back, in plain arithmetic so that every
    backend rounds alike: the nearest precision below bfloat16."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    y = x / scale
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    ulp = jnp.exp2(e - 3.0)
    return jnp.round(y / ulp) * ulp * scale


def _identity(x):
    return x


@functools.partial(jax.jit, static_argnames=("heads", "quant"))
def layer(x, lp, heads: int, quant=_identity):
    t, d = x.shape
    dh = d // heads
    mm = lambda a, b: jnp.matmul(quant(a), quant(b))  # noqa: E731
    h = rmsnorm(x, lp["ln1"])
    q = mm(h, lp["wq"]).reshape(t, heads, dh).transpose(1, 0, 2)
    k = mm(h, lp["wk"]).reshape(t, heads, dh).transpose(1, 0, 2)
    v = mm(h, lp["wv"]).reshape(t, heads, dh).transpose(1, 0, 2)
    s = jnp.einsum("hqd,hkd->hqk", quant(q), quant(k)) / math.sqrt(dh)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,hkd->hqd", quant(p), quant(v))
    x = x + mm(o.transpose(1, 0, 2).reshape(t, d), lp["wo"])
    h = rmsnorm(x, lp["ln2"])
    return x + mm(gelu_tanh(mm(h, lp["w1"])), lp["w2"])


@functools.partial(jax.jit, static_argnames=("quant",))
def head(x, ln_f, emb, positions, quant=_identity):
    xs = rmsnorm(x[positions], ln_f)
    return jnp.matmul(quant(xs), quant(emb).T)


def logits_at(params: dict, heads: int, tokens, positions, pad_to: int,
              quant=_identity):
    """Float32 logits after the given ``positions`` of ``tokens`` (1-D ints).
    The sequence is padded to ``pad_to`` so every call has one shape; the
    padding lies after every real position and is causally invisible."""
    toks = np.zeros(pad_to, np.int32)
    toks[:len(tokens)] = tokens
    n_layers = sum(1 for k in params if k.startswith("l") and k[1:].isdigit())
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["emb"], jnp.asarray(toks), axis=0)
        for i in range(n_layers):
            x = layer(x, params[f"l{i}"], heads=heads, quant=quant)
        return head(x, params["ln_f"], params["emb"],
                    jnp.asarray(positions, jnp.int32), quant=quant)


def served_gaps(params: dict, heads: int, tokens, n_prompt: int, pad_to: int,
                max_out: int, control: bool = False) -> dict:
    """For one served request (``tokens`` = prompt + served tokens): at every
    served position, how far the served token's reference logit lies below
    the reference's best. With ``control``, the same for the token that the
    float8 control puts first at that position."""
    tokens = np.asarray(tokens, np.int64)
    n_out = len(tokens) - n_prompt
    pos = np.full(max_out, n_prompt - 1, np.int32)
    pos[:n_out] = np.arange(n_prompt - 1, len(tokens) - 1)
    ref = np.asarray(logits_at(params, heads, tokens[:-1], pos, pad_to))[:n_out]
    best = ref.max(axis=-1)
    served = tokens[n_prompt:]
    out = {"gaps": best - ref[np.arange(n_out), served],
           "argmax_agree": float((ref.argmax(-1) == served).mean())}
    if control:
        low = np.asarray(logits_at(params, heads, tokens[:-1], pos, pad_to,
                                   quant=fp8_operand))[:n_out]
        out["control_gaps"] = best - ref[np.arange(n_out), low.argmax(-1)]
    return out
