"""A decoder described by a spec built from a published configuration.

``transformer.py`` has one block, fixed by ``heads: int``: head size tied to
the width, no positional term, a two-matrix GELU FFN, a tied head. The models
people serve mix layer kinds, so this module describes a model by a frozen,
hashable :class:`ModelSpec` (static under ``jit``) read from a Hugging Face
style configuration dict (:meth:`ModelSpec.from_config`):

- a head size independent of the width, and query heads **per layer**;
- layers of two attention kinds, ``full`` (every earlier position) and
  ``sliding`` (the last ``window`` positions: key ``j`` is visible to query
  ``i`` iff ``0 <= i - j < window``), each with its own rotary embedding
  (plain, or YaRN on part of each head);
- a per-head sigmoid gate on the attention output, before ``wo``;
- a SwiGLU FFN, dense or a mixture of experts with a shared expert
  (:func:`~marlin_tpu.models.moe.moe_experts_ffn`: the layer is told which
  experts it holds and computes their part of the result);
- an untied head over the rows of the vocabulary that are held here;
- parameters in ``param_dtype`` (norm gains and the router in float32);
  matmul operands and the KV cache in ``compute_dtype``; the residual
  stream, the norms, the router, the rotary embedding, the gate and every
  softmax in float32 (a bfloat16 residual stream would round the whole
  stream at every layer; the float32 one costs nothing measurable on the
  chip, ``PERF.md`` section 6, PR 31).

The block's arithmetic exists once (:func:`layer_forward`); what differs
between the two paged programs is only how a query meets the cache, handed in
as ``attend``. Only the paged serving path runs such a model
(``ServeEngine(params, spec)`` -> ``lm_prefill_paged`` / ``lm_decode_paged``,
which hand a spec to :func:`prefill_paged` / :func:`decode_paged` here):
``lm_generate`` and the trainer raise for a spec
(:func:`require_int_heads`).

Two classes of KV page (``serving/kvpool.py``): a full layer's slab is
indexed by the row's *global* table (every position); a sliding layer's slab
by the row's *window* table, a ring of :func:`window_ring_pages` pages in
which position ``p`` lives in slot ``(p // page_len) % ring``.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["RopeSpec", "LayerSpec", "ModelSpec", "init_params",
           "init_layer_params", "init_kv_pages", "window_ring_pages",
           "layer_forward", "prefill_paged", "decode_paged",
           "require_int_heads"]

_MASKED = -1e30  # as ops/paged_attention.py: exp() underflows to exactly 0


def require_int_heads(heads, what: str) -> None:
    """The paths that run only ``transformer.py``'s own block say so."""
    if isinstance(heads, ModelSpec):
        raise TypeError(
            f"{what} runs only the block that `heads: int` describes; a "
            f"ModelSpec is served by the paged path alone "
            f"(ServeEngine(params, spec) with paged=True)")


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One rotary embedding: ``kind`` ``default`` (``inv_freq_i =
    theta^(-2i/D)``) or ``yarn`` (the interpolated/extrapolated blend below,
    cos and sin scaled by ``attention_factor``), over the first
    ``rotary_dim`` (= D) dimensions of each head, rotate-half form."""

    theta: float
    rotary_dim: int
    kind: str = "default"
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self) -> np.ndarray:
        D = self.rotary_dim
        i = np.arange(0, D, 2, dtype=np.float64)
        extra = 1.0 / self.theta ** (i / D)
        if self.kind == "default":
            return extra.astype(np.float32)
        if self.kind != "yarn":
            raise ValueError(f"unknown rope kind {self.kind!r}")
        inter = extra / self.factor

        def correction(rotations: float) -> float:
            return (D * math.log(self.original_max
                                 / (rotations * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(correction(self.beta_fast)), 0)
        high = min(math.ceil(correction(self.beta_slow)), D - 1)
        if high == low:
            high += 0.001  # as the published implementation: no 0 / 0
        ramp = np.clip((np.arange(D // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    attn: str      # "full" | "sliding"
    q_heads: int
    ffn: str       # "dense" | "moe"


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Everything the programs need to know of a model that is not in the
    parameters' shapes. Hashable: static in ``jit``."""

    d_model: int
    head_dim: int
    kv_heads: int
    layers: tuple
    window: int
    rope_full: RopeSpec
    rope_sliding: RopeSpec
    dense_width: int
    expert_width: int
    shared_width: int
    n_experts: int        # the router's width: every expert of the model
    experts_held: int     # how many of them live here ...
    first_expert: int     # ... starting at this one
    top_k: int
    routed_scale: float
    vocab_held: int
    norm_eps: float = 1e-6
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, cfg: dict, experts_total: int | None = None,
                    first_expert: int = 0) -> "ModelSpec":
        """From the published keys (``hidden_size``, ``head_dim``,
        ``layer_types``, ``num_attention_heads_per_layer``,
        ``mlp_layer_types``, ``rope_parameters``, ...). ``num_hidden_layers``
        layers are taken from the front of the per-layer lists;
        ``num_experts`` and ``vocab_size`` are what is held here.
        ``experts_total`` is the router's width where the configuration holds
        a share of the experts (default: all are held), ``first_expert`` the
        first one of the share."""
        n = int(cfg["num_hidden_layers"])
        kinds = {"full_attention": "full", "sliding_attention": "sliding"}
        heads = cfg.get("num_attention_heads_per_layer") or (
            [cfg["num_attention_heads"]] * n)
        ffns = cfg.get("mlp_layer_types") or (
            ["dense" if i in cfg.get("mlp_only_layers", ()) else "sparse"
             for i in range(n)])
        layers = tuple(
            LayerSpec(kinds[cfg["layer_types"][i]], int(heads[i]),
                      "dense" if ffns[i] == "dense" else "moe")
            for i in range(n))
        dh = int(cfg["head_dim"])

        def rope(p: dict) -> RopeSpec:
            D = int(round(dh * float(p.get("partial_rotary_factor", 1.0))))
            return RopeSpec(
                theta=float(p["rope_theta"]), rotary_dim=D,
                kind=p.get("rope_type", "default"),
                factor=float(p.get("factor", 1.0)),
                original_max=int(p.get("original_max_position_embeddings",
                                       0)),
                beta_fast=float(p.get("beta_fast", 32.0)),
                beta_slow=float(p.get("beta_slow", 1.0)),
                attention_factor=float(p.get("attention_factor", 1.0)))

        held = int(cfg["num_experts"])
        total = held if experts_total is None else int(experts_total)
        if not 0 <= first_expert <= total - held:
            raise ValueError(f"experts [{first_expert}, "
                             f"{first_expert + held}) are not among {total}")
        return cls(
            d_model=int(cfg["hidden_size"]), head_dim=dh,
            kv_heads=int(cfg["num_key_value_heads"]), layers=layers,
            window=int(cfg["sliding_window"]),
            rope_full=rope(cfg["rope_parameters"]["full_attention"]),
            rope_sliding=rope(cfg["rope_parameters"]["sliding_attention"]),
            dense_width=int(cfg["intermediate_size"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            shared_width=int(cfg["shared_expert_intermediate_size"]),
            n_experts=total, experts_held=held,
            first_expert=int(first_expert),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scale=float(cfg.get("moe_routed_scaling_factor", 1.0)),
            vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")))

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def layer_names(self, attn: str) -> list:
        return [f"l{i}" for i, ly in enumerate(self.layers)
                if ly.attn == attn]

    @property
    def has_window(self) -> bool:
        return any(ly.attn == "sliding" for ly in self.layers)


def window_ring_pages(window: int, chunk: int, page_len: int) -> int:
    """Pages of a row's window ring: the pages a query's window can touch
    (``window / page_len`` and one more when it straddles a boundary), and no
    fewer than one prefill chunk writes at a time."""
    if window % page_len:
        raise ValueError(f"page_len {page_len} must divide the window "
                         f"{window}")
    return max(window // page_len + 1, -(-chunk // page_len))


# ----------------------------------------------------------------- parameters


def _compile_side_by_side(calls) -> None:
    """Compile the programs that the given calls of jitted functions
    (``(jitted, args, kwargs)`` each) will run, all at once; nothing
    executes and no buffer is donated. Each is lowered here, one after
    another, and its lowering handed to a thread of its own to compile: the
    compiler leaves most of a host's cores idle on one program. Lowered in
    threads, the modules' text (and with it the persistent compilation
    cache's key) changed from run to run, and tracing holds the
    interpreter's lock anyway. The calls themselves then find their
    programs compiled: ``jit`` keeps the executable of a lowering it has
    made for the same arguments."""
    from concurrent.futures import ThreadPoolExecutor

    if len(calls) < 2:
        return
    with ThreadPoolExecutor(len(calls)) as pool:
        for done in [pool.submit(fn.lower(*args, **kwargs).compile)
                     for fn, args, kwargs in calls]:
            done.result()


def _normal(key, shape, std, dtype):
    """Drawn in float32, kept in ``dtype``: a bfloat16 draw has 256 values a
    binade."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


@functools.partial(jax.jit, static_argnames=("spec", "ly"))
def init_layer_params(spec: ModelSpec, ly: LayerSpec, key) -> dict:
    """One layer's parameters from ``key``, in one jitted draw (a whole
    model's experts through float32 at once would not fit beside them); one
    compile per kind of layer."""
    d, dh, dt = spec.d_model, spec.head_dim, jnp.dtype(spec.param_dtype)
    ks = jax.random.split(key, 16)
    s = d ** -0.5
    hq, hk = ly.q_heads * dh, spec.kv_heads * dh
    lp = {"ln1": jnp.ones((d,), jnp.float32),
          "ln2": jnp.ones((d,), jnp.float32),
          "wq": _normal(ks[0], (d, hq), s, dt),
          "wk": _normal(ks[1], (d, hk), s, dt),
          "wv": _normal(ks[2], (d, hk), s, dt),
          "wgate": _normal(ks[3], (d, ly.q_heads), s, dt),
          "wo": _normal(ks[4], (hq, d), hq ** -0.5, dt)}
    if ly.ffn == "dense":
        f = spec.dense_width
        lp.update(w_gate=_normal(ks[5], (d, f), s, dt),
                  w_up=_normal(ks[6], (d, f), s, dt),
                  w_down=_normal(ks[7], (f, d), f ** -0.5, dt))
    else:
        fe, fs, e = spec.expert_width, spec.shared_width, spec.experts_held
        lp["moe"] = {
            "router": jax.random.normal(ks[8], (d, spec.n_experts),
                                        jnp.float32) * s,
            "e_gate": _normal(ks[9], (e, d, fe), s, dt),
            "e_up": _normal(ks[10], (e, d, fe), s, dt),
            "e_down": _normal(ks[11], (e, fe, d), fe ** -0.5, dt),
            "s_gate": _normal(ks[12], (d, fs), s, dt),
            "s_up": _normal(ks[13], (d, fs), s, dt),
            "s_down": _normal(ks[14], (fs, d), fs ** -0.5, dt)}
    return lp


def init_params(spec: ModelSpec, key) -> dict:
    """Scaled-normal parameters, drawn a layer at a time; embedding and head
    are separate (untied) and hold ``vocab_held`` rows."""
    dt = jnp.dtype(spec.param_dtype)
    ks = jax.random.split(key, spec.n_layers + 2)
    p = {"emb": _normal(ks[0], (spec.vocab_held, spec.d_model), 0.02, dt),
         "head": _normal(ks[1], (spec.vocab_held, spec.d_model),
                         spec.d_model ** -0.5, dt),
         "ln_f": jnp.ones((spec.d_model,), jnp.float32)}
    if not isinstance(ks, jax.core.Tracer):
        _compile_side_by_side([(init_layer_params, (spec, ly, ks[2]), {})
                               for ly in dict.fromkeys(spec.layers)])
    for i in range(spec.n_layers):
        p[f"l{i}"] = init_layer_params(spec, spec.layers[i], ks[2 + i])
    return p


def init_kv_pages(spec: ModelSpec, num_pages: int, window_pages: int,
                  page_len: int, compute_dtype: str | None = None) -> dict:
    """Zeroed slabs, layer -> (k, v): ``(num_pages, page_len, kv_heads,
    head_dim)`` for a full layer, ``(window_pages, ...)`` for a sliding one.
    Page 0 of each class is its dummy."""
    if num_pages < 2 or (spec.has_window and window_pages < 2):
        raise ValueError(f"each page class needs >= 2 pages (page 0 is the "
                         f"dummy), got {num_pages} and {window_pages}")
    dt = jnp.dtype(compute_dtype or spec.compute_dtype)
    return {f"l{i}": tuple(
        jnp.zeros((num_pages if ly.attn == "full" else window_pages,
                   page_len, spec.kv_heads, spec.head_dim), dt)
        for _ in range(2)) for i, ly in enumerate(spec.layers)}


# ------------------------------------------------------------ the block, once


def _rmsnorm(x, g, eps: float):
    """Float32 in, float32 out: the caller rounds for its matmuls."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * g.astype(jnp.float32)


def _mm(a, w, out=None):
    """``a @ w`` with ``w`` in ``a``'s dtype, accumulated in float32 and
    kept so where ``out`` says."""
    return jnp.matmul(a, w.astype(a.dtype),
                      preferred_element_type=jnp.float32).astype(
                          out or a.dtype)


def _rope(x, positions, rope: RopeSpec):
    """Rotate the first ``rotary_dim`` dimensions of every head of ``x``
    (T, heads, head_dim) at ``positions`` (T,); float32 inside."""
    D = rope.rotary_dim
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(rope.inv_freq())[None, :])          # (T, D/2)
    cos = (jnp.cos(ang) * rope.attention_factor)[:, None, :]
    sin = (jnp.sin(ang) * rope.attention_factor)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :D // 2], xf[..., D // 2:D]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xf[..., D:]], axis=-1)
    return out.astype(x.dtype)


def _swiglu(h, w_gate, w_up, w_down):
    """Operands in ``h``'s dtype, the result in float32 (it joins the
    residual stream)."""
    return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down,
               jnp.float32)


def layer_forward(spec: ModelSpec, i: int, lp: dict, x, positions, valid,
                  attend):
    """Layer ``i`` over the float32 residual stream ``x`` (T, d) whose rows
    stand at ``positions`` (T,): the one place the block's arithmetic is
    written. ``attend(q, k, v)``
    takes the rotated ``q`` (T, kv_heads, group, head_dim) and this layer's
    new ``k``, ``v`` (T, kv_heads, head_dim), stores them where the program
    keeps its cache, and returns the attention output in ``q``'s shape.
    ``valid`` (T,) marks the rows that are real tokens: the others are routed
    to no expert. Returns ``(x, counts)``; ``counts`` is the expert layer's
    ``(assignments, local assignments, held experts touched)``, zeros for a
    dense FFN."""
    ly = spec.layers[i]
    T, cd = x.shape[0], jnp.dtype(spec.compute_dtype)
    H, kvh, dh = ly.q_heads, spec.kv_heads, spec.head_dim
    rope = spec.rope_full if ly.attn == "full" else spec.rope_sliding
    with jax.named_scope(f"attn_{ly.attn}"):
        h = _rmsnorm(x, lp["ln1"], spec.norm_eps).astype(cd)
        q = _rope(_mm(h, lp["wq"]).reshape(T, H, dh), positions, rope)
        k = _rope(_mm(h, lp["wk"]).reshape(T, kvh, dh), positions, rope)
        v = _mm(h, lp["wv"]).reshape(T, kvh, dh)
        gate = jax.nn.sigmoid(_mm(h, lp["wgate"], jnp.float32))
        o = attend(q.reshape(T, kvh, H // kvh, dh), k, v).reshape(T, H, dh)
        o = (o.astype(jnp.float32) * gate[:, :, None]).astype(cd)
        x = x + _mm(o.reshape(T, H * dh), lp["wo"], jnp.float32)
    h = _rmsnorm(x, lp["ln2"], spec.norm_eps)
    if ly.ffn == "dense":
        with jax.named_scope("ffn_dense"):
            return (x + _swiglu(h.astype(cd), lp["w_gate"], lp["w_up"],
                                lp["w_down"]),
                    jnp.zeros((3,), jnp.int32))
    from .moe import moe_experts_ffn

    with jax.named_scope("moe_experts"):
        out, counts = moe_experts_ffn(
            lp["moe"], h, valid, top_k=spec.top_k,
            first_expert=spec.first_expert, routed_scale=spec.routed_scale,
            compute_dtype=cd)
    return x + out, counts


def _head_logits(spec: ModelSpec, params: dict, x):
    """Float32 logits over the held rows of the (untied) head."""
    xf = _rmsnorm(x, params["ln_f"], spec.norm_eps).astype(
        spec.compute_dtype)
    return jnp.matmul(xf, params["head"].astype(xf.dtype).T,
                      preferred_element_type=jnp.float32)


def _attend_dense(q, k, v, q_pos, k_pos, k_live, window, block=None):
    """Masked softmax attention of ``q`` (T, kvh, g, dh) over ``k``/``v``
    (L, kvh, dh), one KV head at a time (the scores of all heads of a long
    bucket at once would be gigabytes): key ``j`` is visible to query ``i``
    iff it is live, not ahead of it and, with a ``window``, less than
    ``window`` behind. With ``block`` the keys (in ascending position) are
    met ``block`` at a time with a running softmax, and a block that begins
    after the last query is skipped: a chunk early in a long bucket's table
    pays for the positions before it, not for the table."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    T, L = q.shape[0], k.shape[0]

    def seen(pos, live):
        ok = live[None, :] & (pos[None, :] <= q_pos[:, None])
        if window is not None:
            ok &= q_pos[:, None] - pos[None, :] < window
        return ok

    def scores(qh, kh, pos, live):
        s = jnp.einsum("pgd,td->gpt", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        return jnp.where(seen(pos, live)[None], s, _MASKED)

    if block is None or L <= block:
        def one(qkv):
            qh, kh, vh = qkv                   # (T, g, dh), (L, dh), (L, dh)
            p = jax.nn.softmax(scores(qh, kh, k_pos, k_live), axis=-1)
            return jnp.einsum("gpt,td->pgd", p.astype(qh.dtype), vh)
    else:
        nb = -(-L // block)
        pad = nb * block - L

        def blocks(x, fill=0):
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                        constant_values=fill)
            return x.reshape(nb, block, *x.shape[1:])

        pos_b, live_b = blocks(k_pos), blocks(k_live, False)

        def one(qkv):
            qh, kh, vh = qkv
            g = qh.shape[1]

            def step(carry, blk):
                kb, vb, pb, lb = blk

                def meet(carry):
                    m, l, acc = carry
                    s = scores(qh, kb, pb, lb)             # (g, T, block)
                    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new[..., None])
                    pv = jnp.einsum("gpt,td->gpd", p.astype(qh.dtype), vb,
                                    preferred_element_type=jnp.float32)
                    return (m_new, alpha * l + jnp.sum(p, axis=-1),
                            acc * alpha[..., None] + pv)

                return jax.lax.cond(pb[0] <= q_pos[-1], meet,
                                    lambda c: c, carry), None

            init = (jnp.full((g, T), _MASKED, jnp.float32),
                    jnp.zeros((g, T), jnp.float32),
                    jnp.zeros((g, T, qh.shape[-1]), jnp.float32))
            (_, l, acc), _ = jax.lax.scan(
                step, init, (blocks(kh), blocks(vh), pos_b, live_b))
            return (acc / l[..., None]).transpose(1, 0, 2).astype(qh.dtype)

    o = jax.lax.map(one, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                          v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3)


# ------------------------------------------------------------- paged prefill


@functools.partial(jax.jit, static_argnames=("spec", "page_len"),
                   donate_argnums=(1,))
def _lm_prefill_paged_spec_jit(params, pages, gtable, wtable, chunk,
                               chunk_start, length, seed, temperature, top_p,
                               top_k, spec: ModelSpec, page_len: int):
    from .transformer import _pick_token_row, _row_key

    C = chunk.shape[0]
    if C % page_len:
        raise ValueError(f"chunk width {C} must be a multiple of page_len "
                         f"{page_len}")
    cp = C // page_len
    kvh, dh = spec.kv_heads, spec.head_dim
    s_page = chunk_start // page_len
    q_pos = chunk_start + jnp.arange(C)
    valid = q_pos < length
    ring = wtable.shape[0]
    wp = spec.window // page_len if spec.has_window else 0
    qb = min(C, max(page_len, C // 4))  # a sliding layer's query sub-block
    # the context a chunk reads, gathered up front and pinned (see
    # transformer._lm_prefill_paged_jit: the slab must not be re-laid-out):
    # a full layer's whole table; a sliding layer's ring slots that hold the
    # window/page_len pages before the chunk, in position order
    Lg = gtable.shape[0] * page_len
    w_slots = wtable[jnp.mod(s_page - wp + jnp.arange(wp), max(ring, 1))]
    ctx = jax.lax.optimization_barrier({
        name: tuple(t[gtable if ly.attn == "full" else w_slots]
                    .reshape(-1, kvh, dh) for t in pages[name])
        for name, ly in ((f"l{i}", ly) for i, ly in enumerate(spec.layers))})
    g_pos = jnp.arange(Lg)
    w_pos = jnp.concatenate([(s_page - wp) * page_len
                             + jnp.arange(wp * page_len), q_pos])
    x = params["emb"][chunk].astype(jnp.float32)
    new_kv, counts = {}, jnp.zeros((3,), jnp.int32)
    for i, ly in enumerate(spec.layers):
        name = f"l{i}"

        def attend(q, k, v, name=name, ly=ly):
            new_kv[name] = (k, v)
            ck, cv = ctx[name]
            if ly.attn == "full":
                ck = jax.lax.dynamic_update_slice(
                    ck, k.astype(ck.dtype), (chunk_start, 0, 0))
                cv = jax.lax.dynamic_update_slice(
                    cv, v.astype(cv.dtype), (chunk_start, 0, 0))
                return _attend_dense(q, ck, cv, q_pos, g_pos,
                                     jnp.ones((Lg,), bool), None, block=C)
            ck = jnp.concatenate([ck, k.astype(ck.dtype)])
            cv = jnp.concatenate([cv, v.astype(cv.dtype)])
            # a band, not a square: the queries a sub-block at a time, each
            # against the window before it and itself (the context holds
            # exactly `window` positions before the chunk, so the slices
            # are static)
            outs = []
            for o in range(0, C, qb):
                keys = slice(o, o + spec.window + qb)
                outs.append(_attend_dense(
                    q[o:o + qb], ck[keys], cv[keys], q_pos[o:o + qb],
                    w_pos[keys], w_pos[keys] >= 0, spec.window))
            return jnp.concatenate(outs)

        x, c = layer_forward(spec, i, params[name], x, q_pos, valid, attend)
        counts = counts + c
    # write the chunk's pages, one dynamic update a page (transformer.py has
    # the reason). A page wholly past the prompt goes to the dummy: in a
    # ring its slot may still hold a page the window needs
    new_pages = {}
    for i, ly in enumerate(spec.layers):
        name = f"l{i}"
        pk, pv = pages[name]
        k, v = new_kv[name]
        pgk = k.astype(pk.dtype).reshape(cp, page_len, kvh, dh)
        pgv = v.astype(pv.dtype).reshape(cp, page_len, kvh, dh)
        for j in range(cp):
            pid = (gtable[s_page + j] if ly.attn == "full"
                   else wtable[jnp.mod(s_page + j, ring)])
            pid = jnp.where(chunk_start + j * page_len < length, pid, 0)
            pk = jax.lax.dynamic_update_index_in_dim(pk, pgk[j], pid, 0)
            pv = jax.lax.dynamic_update_index_in_dim(pv, pgv[j], pid, 0)
        new_pages[name] = (pk, pv)
    idx = jnp.clip(length - 1 - chunk_start, 0, C - 1)
    logits = _head_logits(spec, params, x[idx])
    first = _pick_token_row(temperature, top_p, top_k, logits,
                            _row_key(seed, 0))
    return new_pages, first, counts, logits


def _prefill_args(params, pages, tables, chunk, chunk_start, length,
                  spec: ModelSpec, page_len: int, seed=0, temperature=0.0,
                  top_p=None, top_k=None):
    gtable, wtable = tables
    return (params, pages, jnp.asarray(gtable, jnp.int32),
            jnp.asarray(wtable, jnp.int32), jnp.asarray(chunk, jnp.int32),
            jnp.asarray(chunk_start, jnp.int32),
            jnp.asarray(length, jnp.int32), jnp.asarray(seed, jnp.uint32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
            jnp.asarray(0 if top_k is None else top_k, jnp.int32)), {
                "spec": spec, "page_len": page_len}


def prefill_paged(params, pages, tables, chunk, chunk_start, length,
                  spec: ModelSpec, page_len: int, seed=0, temperature=0.0,
                  top_p=None, top_k=None):
    """:func:`~marlin_tpu.models.transformer.lm_prefill_paged` for a spec:
    ``tables`` is the row's ``(global table, window ring)``. Returns
    ``(pages, first, counts, logits)``: ``counts`` the expert layers'
    ``(assignments, local assignments, experts touched)`` summed over
    layers, ``logits`` the float32 logits ``first`` was picked from."""
    args, static = _prefill_args(params, pages, tables, chunk, chunk_start,
                                 length, spec, page_len, seed, temperature,
                                 top_p, top_k)
    return _lm_prefill_paged_spec_jit(*args, **static)


# -------------------------------------------------------------- paged decode


def _attend_gather(q, pk, pv, tables, lengths, first_page, lower,
                   page_len: int):
    """The reference formulation of the decode kernel: each row's pages
    gathered in position order (ring slot ``(first_page + w) % W``), dense
    masked softmax over them. ``q`` (B, kvh, g, dh)."""
    B, W = tables.shape
    slots = jnp.mod(first_page[:, None] + jnp.arange(W)[None, :], W)
    pids = jnp.take_along_axis(tables, slots, axis=1)
    k = pk[pids].reshape(B, W * page_len, *pk.shape[2:])
    v = pv[pids].reshape(B, W * page_len, *pv.shape[2:])
    pos = first_page[:, None] * page_len + jnp.arange(W * page_len)[None, :]
    live = (pos >= lower[:, None]) & (pos < lengths[:, None])
    s = jnp.einsum("bkgd,btkd->bkgt", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(live[:, None, None, :], s, _MASKED), axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", p.astype(q.dtype), v)


@functools.partial(jax.jit, static_argnames=("spec", "page_len", "kernel"),
                   donate_argnums=(1,))
def _lm_decode_paged_spec_jit(params, pages, gtables, wtables, positions,
                              cur_tokens, steps_done, seeds, temperature,
                              top_p, top_k, spec: ModelSpec, page_len: int,
                              kernel: str):
    from ..ops.paged_attention import paged_decode_attention
    from .transformer import _pick_token_rows, _scatter_kv_entries

    B, Wg = gtables.shape
    ring = wtables.shape[1]
    rows = jnp.arange(B)
    pos = jnp.minimum(positions, Wg * page_len - 1)
    live = gtables[:, 0] != 0     # a dummy row's table starts at the dummy
    lengths = pos + 1             # the entry written below is live
    page = pos // page_len
    off = pos % page_len
    zero = jnp.zeros((B,), jnp.int32)
    lower = jnp.maximum(pos - spec.window + 1, 0)
    # per class: (tables, the page id this step writes, first page the
    # kernel visits, lowest visible position)
    per_class = {"full": (gtables, gtables[rows, page], zero, zero)}
    if spec.has_window:
        per_class["sliding"] = (wtables, wtables[rows, jnp.mod(page, ring)],
                                lower // page_len, lower)
    x = params["emb"][cur_tokens].astype(jnp.float32)
    new_pages, counts = {}, jnp.zeros((3,), jnp.int32)
    for i, ly in enumerate(spec.layers):
        name = f"l{i}"
        tables, pids, first_page, low = per_class[ly.attn]

        def attend(q, k, v, name=name, ly=ly, tables=tables, pids=pids,
                   first_page=first_page, low=low):
            pk, pv = pages[name]
            pk, pv = _scatter_kv_entries(pk, pv, k.astype(pk.dtype),
                                         v.astype(pv.dtype), pids, off)
            new_pages[name] = (pk, pv)
            if kernel != "pallas":
                return _attend_gather(q, pk, pv, tables, lengths, first_page,
                                      low, page_len)
            if ly.attn == "full":
                return paged_decode_attention(q, pk, pv, tables, lengths)
            return paged_decode_attention(q, pk, pv, tables, lengths,
                                          first_page=first_page, lower=low)

        x, c = layer_forward(spec, i, params[name], x, pos, live, attend)
        counts = counts + c
    logits = _head_logits(spec, params, x)
    nxt = _pick_token_rows(temperature, top_p, top_k, logits, seeds,
                           steps_done)
    return new_pages, nxt, counts, logits


def _decode_args(params, pages, tables, positions, cur_tokens, steps_done,
                 seeds, temperature, top_p, top_k, spec: ModelSpec,
                 page_len: int, kernel: str):
    gtables, wtables = tables
    as_i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    return (params, pages, as_i32(gtables), as_i32(wtables),
            as_i32(positions), as_i32(cur_tokens), as_i32(steps_done),
            jnp.asarray(seeds, jnp.uint32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32), as_i32(top_k)), {
                "spec": spec, "page_len": page_len, "kernel": kernel}


def decode_paged(params, pages, tables, positions, cur_tokens, steps_done,
                 seeds, temperature, top_p, top_k, spec: ModelSpec,
                 page_len: int, kernel: str):
    """:func:`~marlin_tpu.models.transformer.lm_decode_paged` for a spec:
    ``tables`` is ``(global tables (B, W), window rings (B, ring))``; a row
    whose global table starts at the dummy page is a dummy row and is routed
    to no expert. Returns ``(pages, next_tokens, counts, logits)`` as
    :func:`prefill_paged`."""
    args, static = _decode_args(params, pages, tables, positions, cur_tokens,
                                steps_done, seeds, temperature, top_p, top_k,
                                spec, page_len, kernel)
    return _lm_decode_paged_spec_jit(*args, **static)


def precompile_paged(prefills, decodes) -> None:
    """Compile side by side the programs that the given
    :func:`prefill_paged` / :func:`decode_paged` calls (one argument tuple
    each) will run (:func:`_compile_side_by_side`): one program of nine
    unrolled layers with their kernels takes the compiler half a minute, and
    a bucketed engine has six."""
    _compile_side_by_side(
        [(_lm_prefill_paged_spec_jit, *_prefill_args(*a)) for a in prefills]
        + [(_lm_decode_paged_spec_jit, *_decode_args(*a)) for a in decodes])


prefill_paged._cache_size = _lm_prefill_paged_spec_jit._cache_size
decode_paged._cache_size = _lm_decode_paged_spec_jit._cache_size
