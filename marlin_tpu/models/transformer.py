"""A minimal causal transformer LM wired for long-context training.

No reference analog (the reference's only DNN is the 2-layer MLP,
examples/NeuralNetwork.scala) — this model exists because the task's
long-context mandate makes "can you actually TRAIN with sequence-parallel
attention" a first-class capability, and the pieces are all in the library:
ring/ulysses attention (differentiable, sharded over the mesh),
``jax.checkpoint`` rematerialization, optax optimizers, and the checkpoint
subsystem. The regime is context parallelism: ONE long sequence sharded over
the device ring per step (batch-of-one is the long-context training shape —
batching multiplies memory exactly where sequence length already did).

Everything is a pure function over a params pytree; one jitted step per
(config, mesh).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .hybrid import ModelSpec, require_int_heads

__all__ = ["TransformerLM", "init_transformer", "transformer_forward",
           "lm_loss", "lm_train_step", "lm_generate", "lm_generate_batch",
           "init_kv_pages", "lm_prefill_paged", "lm_decode_paged",
           "kv_page_copy", "synthetic_stream"]


def synthetic_stream(seq: int, vocab: int = 64, seed: int = 0,
                     period: int = 8, step: int = 3,
                     noise: float = 0.1) -> np.ndarray:
    """A learnable token stream for demos/tests: a short repeating pattern
    with a ``noise`` fraction of random tokens — enough structure that a few
    training steps measurably drop the loss."""
    rng = np.random.default_rng(seed)
    base = np.tile(np.arange(period) * step % vocab, seq // period + 1)[:seq]
    rand = rng.integers(0, vocab, seq)
    return np.where(rng.random(seq) < 1.0 - noise, base, rand).astype(np.int32)


def init_transformer(key, vocab: int, d_model: int, heads: int, layers: int,
                     d_ff: int | None = None, dtype=jnp.float32,
                     kv_heads: int | None = None, n_experts: int | None = None,
                     moe_every: int = 1) -> dict:
    """Scaled-normal init; tied input/output embedding. ``kv_heads`` enables
    grouped-query attention: ``heads // kv_heads`` query heads share one K/V
    head (wk/wv project to ``kv_heads·dh``), which divides the decode KV
    cache — THE decode memory — and the K/V projection params/FLOPs by the
    group factor. (Training-time attention broadcasts K/V back to the query
    head count inside the block, so the in-attention activations stay
    full-size there — the knob is a serving lever.) Every consumer derives
    the K/V head count from the parameter shapes, so GQA needs no signature
    changes anywhere downstream.

    ``n_experts`` switches the FFN of every ``moe_every``-th layer (counting
    from layer ``moe_every - 1``; the default 1 = every layer) to a
    mixture-of-experts with that many experts (:mod:`.moe` — router + per-
    expert FFN params under the layer's ``"moe"`` key, in place of w1/w2).
    Routing-time knobs (top_k / capacity / grouping) live in the forward's
    ``moe`` argument, not in the params."""
    d_ff = d_ff or 4 * d_model
    kvh = heads if kv_heads is None else kv_heads
    if kvh < 1 or heads % kvh:
        raise ValueError(f"kv_heads ({kvh}) must divide heads ({heads})")
    if moe_every < 1:
        raise ValueError(f"moe_every must be >= 1, got {moe_every}")
    kv_dim = (d_model // heads) * kvh
    ks = jax.random.split(key, 2 + 6 * layers)
    p = {"emb": jax.random.normal(ks[0], (vocab, d_model), dtype) * 0.02}
    for i in range(layers):
        k = ks[2 + 6 * i: 8 + 6 * i]
        s = 1.0 / math.sqrt(d_model)
        lp = {
            "wq": jax.random.normal(k[0], (d_model, d_model), dtype) * s,
            "wk": jax.random.normal(k[1], (d_model, kv_dim), dtype) * s,
            "wv": jax.random.normal(k[2], (d_model, kv_dim), dtype) * s,
            "wo": jax.random.normal(k[3], (d_model, d_model), dtype) * s,
            "ln1": jnp.ones((d_model,), dtype),
            "ln2": jnp.ones((d_model,), dtype),
        }
        if n_experts is not None and (i + 1) % moe_every == 0:
            from .moe import init_moe

            lp["moe"] = init_moe(k[4], d_model, d_ff, n_experts, dtype)
        else:
            lp["w1"] = jax.random.normal(k[4], (d_model, d_ff), dtype) * s
            lp["w2"] = (jax.random.normal(k[5], (d_ff, d_model), dtype)
                        / math.sqrt(d_ff))
        p[f"l{i}"] = lp
    p["ln_f"] = jnp.ones((d_model,), dtype)
    return p


def _n_layers(params: dict) -> int:
    """Layer count from the params dict — THE accessor for the l{i} naming
    scheme (transformer trunk, decode, prefill, and the pipeline trainer all
    count through here)."""
    return sum(1 for k in params if k.startswith("l") and k[1:].isdigit())


def _rmsnorm(x, g):
    """Statistics in f32 regardless of the activation dtype (bf16 squares
    underflow/overflow too readily); output back in the input's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
    return (y * g).astype(x.dtype)


_ATTN_BACKENDS = {"ring": "auto", "ring_flash": "flash", "ring_xla": "xla"}

# (top_k, capacity_factor, group_size) when a model has MoE layers but the
# caller didn't pass routing knobs — one place, shared by train + prefill
_MOE_DEFAULTS = (2, 1.25, 4096)


def _mlp(h, w1, w2, chunk: int | None):
    """The position-wise FFN, optionally scanned over ``chunk``-token slices
    with per-slice rematerialization. The (seq, d_ff) GELU intermediate is
    the single largest activation in the block (d_ff = 4d); chunking caps it
    at (chunk, d_ff) — the :func:`_chunked_nll` trick applied to the FFN
    (compiler-measured: ~0.9 GiB off the 1M-token f32 step at d_ff=1024;
    grows with d_ff). Positions are independent, so slicing is exact."""
    if chunk is not None and chunk < 1:
        raise ValueError(f"mlp_chunk must be >= 1 or None, got {chunk}")
    if chunk is None or h.shape[0] <= chunk:
        return jax.nn.gelu(h @ w1) @ w2

    def one(hc):
        return jax.nn.gelu(hc @ w1) @ w2

    seq, d = h.shape
    n_full = seq // chunk
    head = h[: n_full * chunk].reshape(n_full, chunk, d)
    body = jax.checkpoint(lambda _, hc: (None, one(hc)))
    _, out = jax.lax.scan(body, None, head)
    out = out.reshape(n_full * chunk, d)
    if seq % chunk:
        out = jnp.concatenate([out, one(h[n_full * chunk:])])
    return out


def _block(lp, x, heads: int, mesh, attn: str, precision: str,
           mlp_chunk: int | None = None, moe: tuple | None = None):
    # No explicit sequence-sharding constraints here: XLA's sharding
    # propagation from the ring's internal placements already shards the
    # residual stream and projections over the mesh rows axis (verified by
    # per-chip compiler accounting — adding constraints changed nothing,
    # AOT_MEMORY.json), and explicit constraints reject sequence lengths
    # that don't divide the axis (training lengths are seq-1).
    from ..parallel.ring_attention import ring_attention
    from ..parallel.ulysses import ulysses_attention

    seq, d = x.shape
    dh = d // heads
    cd = x.dtype  # activations carry the compute dtype; params stay f32
    h = _rmsnorm(x, lp["ln1"])

    def split_heads(w):
        nh = w.shape[1] // dh  # kv_heads < heads under GQA (init_transformer)
        return (h @ w.astype(cd)).reshape(seq, nh, dh).transpose(1, 0, 2)

    q, k, v = split_heads(lp["wq"]), split_heads(lp["wk"]), split_heads(lp["wv"])
    if k.shape[0] != heads:
        # GQA: each group of query heads attends to its shared K/V head —
        # broadcast K/V up to the query head count for the attention engines
        # (the softmax math is exactly MQA/GQA; the projection/cache savings
        # happened above, at the wk/wv matmuls)
        group = heads // k.shape[0]
        k, v = (jnp.repeat(t, group, axis=0) for t in (k, v))
    if attn in _ATTN_BACKENDS:
        o = ring_attention(q, k, v, mesh, causal=True, precision=precision,
                           backend=_ATTN_BACKENDS[attn])
    else:
        o = ulysses_attention(q, k, v, mesh, causal=True, precision=precision)
    o = o.transpose(1, 0, 2).reshape(seq, d).astype(cd) @ lp["wo"].astype(cd)
    x = x + o
    h = _rmsnorm(x, lp["ln2"])
    if "moe" in lp:
        from .moe import moe_ffn

        tk, cf, gs = moe if moe is not None else _MOE_DEFAULTS
        out, aux = moe_ffn(lp["moe"], h, mesh=mesh, top_k=tk,
                           capacity_factor=cf, group_size=gs,
                           precision=precision)
        return x + out, aux
    return (x + _mlp(h, lp["w1"].astype(cd), lp["w2"].astype(cd), mlp_chunk),
            jnp.zeros((), jnp.float32))


def transformer_forward(params: dict, tokens, mesh=None, heads: int = 4,
                        attn: str = "ring", remat: bool = False,
                        precision: str = "high",
                        compute_dtype: str | None = None,
                        mlp_chunk: int | None = None,
                        offload_residuals: bool = False,
                        moe: tuple | None = None):
    """Logits for next-token prediction; ``tokens`` is a (seq,) int array.
    ``attn``: "ring" (sequence rotates K/V panels; backend auto-picked),
    "ring_flash" / "ring_xla" (ring with the backend pinned), or "ulysses"
    (heads re-shard via all_to_all; needs heads % mesh-axis == 0). ``remat``
    rematerializes each block in the backward — the HBM knob for long
    sequences. ``compute_dtype`` (e.g. "bfloat16") runs the *activations*
    through that dtype while params/optimizer stay f32 — the other half of
    the long-context HBM budget (activations dominate it; see
    docs/parallelism.md) and the bf16-MXU speed path. ``offload_residuals``
    parks the remat checkpoints in host RAM (:func:`_trunk`). ``moe``:
    (top_k, capacity_factor, group_size) routing knobs for MoE layers
    (models with ``n_experts``; ignored otherwise — the load-balance aux
    term is a training concern, see :func:`lm_loss`)."""
    x, _ = _trunk(params, tokens, mesh, heads, attn, remat, precision,
                  compute_dtype, mlp_chunk, offload_residuals, moe)
    return _head_logits(x, params["emb"])


def _head_logits(x, emb):
    """LM head with f32 logits regardless of the activation dtype: bf16
    operands on the MXU, f32 accumulation — never a bf16-rounded logit
    tensor (near-tied logits would lose resolution for zero memory win)."""
    return jnp.matmul(x, emb.T.astype(x.dtype),
                      preferred_element_type=jnp.float32)


def _trunk(params, tokens, mesh, heads, attn, remat, precision,
           compute_dtype=None, mlp_chunk=None, offload_residuals=False,
           moe=None):
    """Final-rmsnorm hidden states, (seq, d_model) — the forward minus the
    LM head projection. With ``compute_dtype``, the residual stream and every
    matmul operand are cast to it (norm statistics and softmax stay f32
    inside their ops; the flash kernels accumulate in f32 via
    preferred_element_type). With ``offload_residuals`` (requires ``remat``),
    the per-layer residual checkpoints — the block inputs, the only forward
    state remat keeps — are moved to pinned host RAM between the forward and
    the backward (``save_and_offload_only_these_names``), removing the
    L·S·d term from device HBM entirely: the knob that carries training past
    the single-chip context cliff (docs/parallelism.md; SURVEY §7
    "matrices bigger than HBM")."""
    require_int_heads(heads, "the trainer and transformer_forward")
    from ..mesh import default_mesh

    mesh = mesh or default_mesh()
    if attn not in (*_ATTN_BACKENDS, "ulysses"):
        raise ValueError(f"unknown attention strategy: {attn!r}")
    if offload_residuals and not remat:
        raise ValueError("offload_residuals requires remat=True (without "
                         "remat there are no residual checkpoints to offload)")
    # NOTE: cast AFTER the gather. Casting the (vocab, d) table first reads
    # nicely but measures worse (+1 GiB at 2M tokens in the compiler's
    # accounting: the gather's backward becomes a bf16 scatter + upcast)
    x = params["emb"][jnp.asarray(tokens)]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    n_layers = _n_layers(params)
    blk = functools.partial(_block, heads=heads, mesh=mesh, attn=attn,
                            precision=precision, mlp_chunk=mlp_chunk, moe=moe)
    aux = jnp.zeros((), jnp.float32)
    if remat and offload_residuals:
        # scan over STACKED layers: in a Python loop the inter-block
        # residuals are plain SSA values XLA keeps on device regardless of
        # any offload annotation (measured: device peak ROSE ~2x), but as a
        # scan carry they are policy-controlled residuals — named via
        # checkpoint_name, saved to pinned_host, fetched back per backward
        # iteration
        from jax.ad_checkpoint import checkpoint_name

        trees = [params[f"l{i}"] for i in range(n_layers)]
        if any(set(t) != set(trees[0]) for t in trees[1:]):
            raise ValueError(
                "offload_residuals stacks the layers into one scan, which "
                "needs uniform layer structure — moe_every > 1 mixes MoE "
                "and dense FFN layers; use moe_every=1 or drop the offload")
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

        def body(h, lp):
            h2, a = blk(lp, checkpoint_name(h, "marlin_resid"))
            return h2, a

        body = jax.checkpoint(body, policy=_OFFLOAD_POLICY())
        x, auxs = jax.lax.scan(body, x, stacked)
        aux = jnp.sum(auxs)
    else:
        for i in range(n_layers):
            b = jax.checkpoint(blk) if remat else blk
            x, a = b(params[f"l{i}"], x)
            aux = aux + a
    return _rmsnorm(x, params["ln_f"]), aux


def _OFFLOAD_POLICY():
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=["marlin_resid"],
        offload_src="device", offload_dst="pinned_host")


def _chunked_nll(x, emb, targets, chunk: int):
    """Summed next-token NLL with the (seq, vocab) logits never materialized:
    a ``lax.scan`` over ``chunk``-token slices, each slice's head projection +
    log-softmax rematerialized in the backward. Peak head memory drops from
    O(seq x vocab) to O(chunk x vocab) — at 1M tokens x 512 vocab that is the
    difference between ~4 GB of logits (+ their cotangents) and ~MBs. The
    sub-chunk remainder is projected outside the scan (shapes are static), so
    no full-tensor pad/copy of ``x`` is ever made."""

    def nll_sum(xc, tc):
        logp = jax.nn.log_softmax(_head_logits(xc, emb), axis=-1)
        return jnp.sum(-jnp.take_along_axis(logp, tc[:, None], axis=1))

    seq = x.shape[0]
    n_full = seq // chunk
    total = jnp.zeros((), jnp.float32)
    if n_full:
        xs = x[: n_full * chunk].reshape(n_full, chunk, x.shape[1])
        ts = targets[: n_full * chunk].reshape(n_full, chunk)
        body = jax.checkpoint(lambda acc, s: (acc + nll_sum(*s), None))
        total, _ = jax.lax.scan(body, total, (xs, ts))
    if seq % chunk:
        total = total + nll_sum(x[n_full * chunk:], targets[n_full * chunk:])
    return total


def lm_loss(params, tokens, mesh=None, heads: int = 4, attn: str = "ring",
            remat: bool = False, precision: str = "high",
            loss_chunk: int | None = None, compute_dtype: str | None = None,
            mlp_chunk: int | None = None, offload_residuals: bool = False,
            moe: tuple | None = None, moe_aux_weight: float = 1e-2):
    """Mean next-token cross-entropy over the sequence. ``loss_chunk`` scans
    the LM head over that many tokens at a time (see :func:`_chunked_nll`) —
    the long-context memory knob companion to ``remat``. ``compute_dtype``
    runs activations in that dtype (loss math itself stays f32);
    ``offload_residuals`` parks the remat checkpoints in host RAM
    (see :func:`_trunk`). For MoE models, ``moe_aux_weight`` times the
    summed Switch load-balance term joins the loss (``moe`` carries the
    routing knobs); dense models contribute an exact zero there."""
    tgt = jnp.asarray(tokens[1:])
    if loss_chunk is not None and loss_chunk < 1:
        raise ValueError(f"loss_chunk must be >= 1 or None, got {loss_chunk}")
    x, aux = _trunk(params, tokens[:-1], mesh, heads, attn, remat, precision,
                    compute_dtype, mlp_chunk, offload_residuals, moe)
    if loss_chunk is None:
        logp = jax.nn.log_softmax(_head_logits(x, params["emb"]), axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, tgt[:, None], axis=1))
    else:
        nll = _chunked_nll(x, params["emb"], tgt, loss_chunk) / tgt.shape[0]
    return nll + moe_aux_weight * aux


@functools.partial(jax.jit, static_argnames=(
    "mesh", "heads", "attn", "remat", "precision", "lr", "loss_chunk",
    "compute_dtype", "mlp_chunk", "offload_residuals", "moe"))
def lm_train_step(params, opt_state, tokens, mesh, heads: int, attn: str,
                  remat: bool, precision: str, lr: float,
                  loss_chunk: int | None = None,
                  compute_dtype: str | None = None,
                  mlp_chunk: int | None = None,
                  offload_residuals: bool = False,
                  moe: tuple | None = None,
                  moe_aux_weight=1e-2):
    """One Adam step, jitted at module level with static config primitives so
    repeated ``train()`` calls (and the bench's warm-up-then-time discipline)
    hit one compiled program — the same cache pattern as
    :func:`marlin_tpu.ml.neural_network.train_step_optax`."""
    import optax

    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(p, tokens, mesh, heads, attn, remat, precision,
                          loss_chunk, compute_dtype, mlp_chunk,
                          offload_residuals, moe, moe_aux_weight)
    )(params)
    updates, opt_state = optax.adam(lr).update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss


def _pick_tokens(temperature, top_p, top_k, logits, sub):
    """Greedy at temperature 0, else top-k -> nucleus (top-p) -> categorical
    over the last axis, so the same helper serves the single-sequence
    (vocab,) and batched (B, vocab) decode paths (one place for the
    clamp/sampling contract). ``top_k`` is static (shapes ``lax.top_k``) and
    ``top_p=None`` statically disables the nucleus filter — the default
    sampling path compiles with no sort; a float ``top_p`` and
    ``temperature`` are traced, so sweeping either reuses one compiled
    program. Both filters run only on the sampled branch (the greedy argmax
    cannot be changed by them)."""

    def sample():
        l = logits / jnp.maximum(temperature, 1e-6)
        if top_k is not None:
            kth = jax.lax.top_k(l, top_k)[0][..., -1:]
            l = jnp.where(l < kth, -jnp.inf, l)
        if top_p is not None:
            # nucleus by RANK, not value: keep the smallest prefix of
            # descending-probability tokens whose exclusive cumulative mass
            # is < top_p (the boundary-crossing token stays, so the set is
            # never empty), then scatter the rank mask back through the
            # inverse permutation — a value cutoff would keep every token
            # TIED with the boundary and silently widen the nucleus
            order = jnp.argsort(-l, axis=-1)  # stable: first max stays first
            srt = jnp.take_along_axis(l, order, axis=-1)
            probs = jax.nn.softmax(srt, axis=-1)
            keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < top_p
            # rank 0 is force-kept: at top_p=0.0 (a traced sweep endpoint no
            # trace-time check can reject) the exclusive-mass test would
            # empty the set and categorical over all -inf degenerates to
            # token 0 — top_p→0 must mean greedy, not garbage
            keep_sorted = keep_sorted.at[..., 0].set(True)
            inv = jnp.argsort(order, axis=-1)
            keep = jnp.take_along_axis(keep_sorted, inv, axis=-1)
            l = jnp.where(keep, l, -jnp.inf)
        return jax.random.categorical(sub, l, axis=-1).astype(jnp.int32)

    return jax.lax.cond(
        temperature > 0.0, sample,
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))


def _decode_step(params, x, caches, pos, heads: int,
                 moe: tuple | None = None):
    """One cached decode position: ``x`` is the (d_model,) embedded token at
    ``pos`` in the compute dtype (the caches and residual stream follow it);
    ``caches`` maps layer -> (k, v) of shape (max_len, kv_heads, dh) —
    ``kv_heads < heads`` under GQA, where the cache IS the decode memory and
    shrinks by the group factor. Attention runs in the grouped form
    (kv_heads, group, ...) with group = heads // kv_heads (plain MHA is the
    group=1 case); the cache prefix is read via position masking (static
    shapes — the scan-friendly decode form of the causal mask);
    scores/softmax are f32."""
    n_layers = _n_layers(params)
    cd = x.dtype
    new_caches = {}
    for i in range(n_layers):
        lp = params[f"l{i}"]
        ck, cv = caches[f"l{i}"]
        d = x.shape[-1]
        dh = d // heads
        kvh = ck.shape[1]
        h = _rmsnorm(x, lp["ln1"])
        q = (h @ lp["wq"].astype(cd)).reshape(kvh, heads // kvh, dh)
        k = (h @ lp["wk"].astype(cd)).reshape(kvh, dh)
        v = (h @ lp["wv"].astype(cd)).reshape(kvh, dh)
        ck = jax.lax.dynamic_update_index_in_dim(ck, k.astype(ck.dtype), pos, 0)
        cv = jax.lax.dynamic_update_index_in_dim(cv, v.astype(cv.dtype), pos, 0)
        s = jnp.einsum("kgd,tkd->kgt", q, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        live = jnp.arange(ck.shape[0]) <= pos
        s = jnp.where(live[None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgt,tkd->kgd", p.astype(cd), cv).reshape(d) \
            @ lp["wo"].astype(cd)
        x = x + o
        h = _rmsnorm(x, lp["ln2"])
        if "moe" in lp:
            # single-token routing is exact (no capacity machinery): gather
            # the chosen experts' weights and combine — see moe_decode_ffn
            from .moe import moe_decode_ffn

            x = x + moe_decode_ffn(
                lp["moe"], h, top_k=(moe or _MOE_DEFAULTS)[0])
        else:
            x = x + jax.nn.gelu(h @ lp["w1"].astype(cd)) @ lp["w2"].astype(cd)
        new_caches[f"l{i}"] = (ck, cv)
    x = _rmsnorm(x, params["ln_f"])
    return _head_logits(x, params["emb"]), new_caches


# Prompts at/above this length prefill through the flash kernel instead of
# the dense (heads, P, P) score einsum. 2048 keeps short prompts on the
# cheaper dense path (the score tensor is a few MB) while bounding score
# memory before the quadratic term matters; at the threshold the dense path
# holds heads x 2048² f32 scores (~32 MB at 2 heads) vs flash's VMEM tiles.
_PREFILL_FLASH_MIN = 2048


def _prefill_attn(q, k, v, cdtype):
    """Causal self-attention over the whole prompt, (P, heads, dh) -> same.

    Short prompts use one batched einsum — the (heads, P, P) f32 score tensor
    is small and XLA fuses the mask/softmax into it. Past
    :data:`_PREFILL_FLASH_MIN` that tensor is quadratic in the prompt (the
    round-4 advisor finding: a long document would OOM at prefill while the
    same length *trains* fine), so the prompt routes through the flash panel
    kernel vmapped over heads — score tiles never leave VMEM and prefill peak
    HBM is linear in P (compiler-asserted in tests/test_aot_tpu.py)."""
    P, heads, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    if P < _PREFILL_FLASH_MIN:
        causal = jnp.tril(jnp.ones((P, P), bool))
        s = jnp.einsum("phd,thd->hpt", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(causal[None], s, -1e30)
        return jnp.einsum("hpt,thd->phd",
                          jax.nn.softmax(s, axis=-1).astype(cdtype), v)

    from ..mesh import pad_to_multiple
    from ..ops.flash_attention import flash_attention_single_panel

    # the flash block contract (ops/flash_attention.block_divisor): > 1024
    # pads to 1024 multiples, shorter to 128; valid_len masks the pad
    pp = pad_to_multiple(P, 1024 if P > 1024 else 128)
    pad = [(0, pp - P), (0, 0)]

    def one_head(qh, kh, vh):
        out, _ = flash_attention_single_panel(
            jnp.pad(qh, pad), jnp.pad(kh, pad), jnp.pad(vh, pad), P,
            causal=True, scale=scale)
        return out

    o = jax.vmap(one_head)(*(jnp.moveaxis(t, 1, 0) for t in (q, k, v)))
    return jnp.moveaxis(o[:, :P], 0, 1).astype(cdtype)


def _prefill_hidden(params, prompt, heads: int, max_len: int, cdtype,
                    moe: tuple | None = None):
    """Process the whole prompt in ONE parallel forward — every projection is
    a (P, d) @ (d, d) MXU matmul and the causal attention is batched (dense
    for short prompts, the flash kernel past :data:`_PREFILL_FLASH_MIN` — see
    :func:`_prefill_attn`) — returning the final-norm hidden states (P, d)
    plus per-layer KV caches (in ``cdtype``) padded to ``max_len``. This is
    the standard prefill/decode split: the scan in :func:`lm_generate` then
    runs only for *generated* tokens (the previous formulation decoded the
    prompt position-by-position, P sequential cache updates that no batch
    dimension could amortize)."""
    n_layers = _n_layers(params)
    P = prompt.shape[0]
    d = params["emb"].shape[1]
    dh = d // heads
    x = params["emb"][prompt].astype(cdtype)
    caches = {}
    for i in range(n_layers):
        lp = params[f"l{i}"]
        kvh = lp["wk"].shape[1] // dh  # kv_heads < heads under GQA
        h = _rmsnorm(x, lp["ln1"])
        q = jnp.reshape(h @ lp["wq"].astype(cdtype), (P, heads, dh))
        k, v = (jnp.reshape(h @ lp[w].astype(cdtype), (P, kvh, dh))
                for w in ("wk", "wv"))
        # caches hold the UNREPEATED kv_heads (the GQA decode-memory win);
        # attention sees the group-broadcast form, as in _block
        caches[f"l{i}"] = tuple(
            jnp.zeros((max_len, kvh, dh), cdtype).at[:P].set(t)
            for t in (k, v))
        if kvh != heads:
            k, v = (jnp.repeat(t, heads // kvh, axis=1) for t in (k, v))
        o = _prefill_attn(q, k, v, cdtype)
        x = x + o.reshape(P, d) @ lp["wo"].astype(cdtype)
        h = _rmsnorm(x, lp["ln2"])
        if "moe" in lp:
            # same grouped routing as training (so prefill states match the
            # training forward); single-device at decode, so no mesh
            from .moe import moe_ffn

            tk, cf, gs = moe if moe is not None else _MOE_DEFAULTS
            mo, _ = moe_ffn(lp["moe"], h, mesh=None, top_k=tk,
                            capacity_factor=cf, group_size=gs)
            x = x + mo
        else:
            x = x + (jax.nn.gelu(h @ lp["w1"].astype(cdtype))
                     @ lp["w2"].astype(cdtype))
    return _rmsnorm(x, params["ln_f"]), caches


def _prefill(params, prompt, heads: int, max_len: int, cdtype,
             moe: tuple | None = None):
    """Final-position logits + caches (the single-sequence prefill form)."""
    x, caches = _prefill_hidden(params, prompt, heads, max_len, cdtype, moe)
    return _head_logits(x[-1], params["emb"]), caches


def lm_generate(params, prompt, key, heads: int, max_len: int, steps: int,
                temperature=0.0, compute_dtype: str | None = None,
                top_p=None, top_k: int | None = None,
                moe: tuple | None = None):
    """KV-cached autoregressive decode: batched prefill of the prompt (one
    parallel forward, :func:`_prefill`), then one ``lax.scan`` sampling
    ``steps`` tokens — the whole generation is a single XLA program.

    ``temperature`` (and ``top_p``, once set to a float) are *traced*
    scalars (greedy at temperature 0; nucleus sampling when ``top_p`` is
    given): sweeping sampling settings reuses one compiled program instead
    of recompiling per value (round-3 verdict #7). ``top_k`` is static (it
    shapes ``lax.top_k``); ``top_p=None`` statically omits the nucleus sort
    from the program (None vs float is a one-time recompile — the sort
    either exists in the program or doesn't).
    ``compute_dtype`` (e.g. "bfloat16") runs the residual stream AND the KV
    caches in that dtype — at decode the caches ARE the memory, so this
    halves cache HBM; logits/softmax stay f32. Defaults to the params
    dtype."""
    require_int_heads(heads, "lm_generate")
    return _lm_generate_jit(
        params, jnp.asarray(prompt, jnp.int32), key, heads=heads,
        max_len=max_len, steps=steps,
        temperature=jnp.asarray(temperature, jnp.float32),
        compute_dtype=compute_dtype,
        top_p=jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
        use_top_p=top_p is not None, top_k=top_k, moe=moe)


@functools.partial(jax.jit, static_argnames=("heads", "max_len", "steps",
                                             "compute_dtype", "use_top_p",
                                             "top_k", "moe"))
def _lm_generate_jit(params, prompt, key, heads: int, max_len: int,
                     steps: int, temperature, compute_dtype,
                     top_p, use_top_p: bool, top_k: int | None,
                     moe: tuple | None = None):
    n_prompt = prompt.shape[0]
    if n_prompt + steps > max_len:
        raise ValueError(
            f"prompt ({n_prompt}) + steps ({steps}) exceeds max_len "
            f"({max_len}); raise max_len or shorten the request")

    pick = functools.partial(_pick_tokens, temperature,
                             top_p if use_top_p else None, top_k)
    cdtype = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype
    logits0, caches = _prefill(params, prompt, heads, max_len, cdtype, moe)
    key, sub = jax.random.split(key)
    first = pick(logits0, sub)
    tokens0 = (jnp.zeros((max_len,), jnp.int32)
               .at[:n_prompt].set(prompt).at[n_prompt].set(first))

    def step(carry, pos):
        tokens, caches, key = carry
        x = params["emb"][tokens[pos]].astype(cdtype)
        logits, caches = _decode_step(params, x, caches, pos, heads, moe)
        key, sub = jax.random.split(key)
        nxt = pick(logits, sub)
        tokens = tokens.at[pos + 1].set(nxt)  # pos+1 <= max_len-1
        return (tokens, caches, key), None

    # positions n_prompt .. n_prompt+steps-2 generate tokens 2..steps
    (tokens, _, _), _ = jax.lax.scan(
        step, (tokens0, caches, key), n_prompt + jnp.arange(steps - 1))
    return tokens[: n_prompt + steps]


def lm_generate_batch(params, prompts, lengths, key, heads: int,
                      max_len: int, steps: int, temperature=0.0,
                      compute_dtype: str | None = None,
                      top_p=None, top_k: int | None = None,
                      moe: tuple | None = None):
    """Batched KV-cached decode: ``prompts`` is (B, P) int32 (rows padded to
    a common P), ``lengths`` (B,) the true prompt lengths — ragged batches
    decode together, each row continuing from ITS OWN position. Returns
    (B, max_len) tokens; row b's generation occupies
    ``[lengths[b], lengths[b] + steps)`` (positions past that hold the pad).

    Decode throughput is batch-driven — the per-step matmuls are (B, d) @
    (d, d) MXU work instead of vector-matrix — so this is the serving shape
    of :func:`lm_generate` (which remains the batch-of-one training-eval
    form). Prefill vmaps the batched flash/dense prefill; per-row cache
    validity is positional (row b's decode step t reads cache entries
    ``<= lengths[b] + t``, so pad entries beyond a short row's length are
    never attended). Sampling knobs as in :func:`lm_generate`
    (``temperature``/``top_p`` traced, ``top_k`` static, ``top_p=None``
    statically sort-free).
    """
    require_int_heads(heads, "lm_generate_batch")
    return _lm_generate_batch_jit(
        params, jnp.asarray(prompts, jnp.int32),
        jnp.asarray(lengths, jnp.int32), key, heads=heads, max_len=max_len,
        steps=steps, temperature=jnp.asarray(temperature, jnp.float32),
        compute_dtype=compute_dtype,
        top_p=jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
        use_top_p=top_p is not None, top_k=top_k, moe=moe)


@functools.partial(jax.jit, static_argnames=("heads", "max_len", "steps",
                                             "compute_dtype", "use_top_p",
                                             "top_k", "moe"))
def _lm_generate_batch_jit(params, prompts, lengths, key, heads: int,
                           max_len: int, steps: int, temperature,
                           compute_dtype, top_p, use_top_p: bool,
                           top_k: int | None, moe: tuple | None = None):
    B, P = prompts.shape
    if P + steps > max_len:
        raise ValueError(
            f"padded prompt ({P}) + steps ({steps}) exceeds max_len "
            f"({max_len}); raise max_len or shorten the request")

    pick = functools.partial(_pick_tokens, temperature,
                             top_p if use_top_p else None, top_k)
    cdtype = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype

    xs, caches = jax.vmap(
        lambda p: _prefill_hidden(params, p, heads, max_len, cdtype,
                                  moe))(prompts)
    hlast = jnp.take_along_axis(
        xs, (lengths - 1)[:, None, None], axis=1)[:, 0]  # (B, d)
    logits0 = _head_logits(hlast, params["emb"])
    key, sub = jax.random.split(key)
    first = pick(logits0, sub)
    rows = jnp.arange(B)
    tokens0 = (jnp.zeros((B, max_len), jnp.int32)
               .at[:, :P].set(prompts).at[rows, lengths].set(first))

    decode = jax.vmap(
        lambda x, c, pos: _decode_step(params, x, c, pos, heads, moe))

    def step(carry, t):
        tokens, caches, key = carry
        pos = lengths + t  # (B,) per-row positions
        x = params["emb"][tokens[rows, pos]].astype(cdtype)
        logits, caches = decode(x, caches, pos)
        key, sub = jax.random.split(key)
        nxt = pick(logits, sub)
        tokens = tokens.at[rows, pos + 1].set(nxt)  # pos+1 <= max_len-1
        return (tokens, caches, key), None

    (tokens, _, _), _ = jax.lax.scan(
        step, (tokens0, caches, key), jnp.arange(steps - 1))
    return tokens


# --------------------------------------------------------------------------
# Row-level sampling for the serving programs below. Unlike the fused
# lm_generate_batch (one program runs a batch to completion — the
# batch-of-prompts eval shape), a serving batch changes composition every
# step: rows enter through prefill and leave individually. Greedy decode
# is composition-independent (each row is the same math as lm_generate's),
# which is what makes per-row results bit-identical to lm_generate on the
# same prompt; sampled rows draw a per-row stream fold_in(key(seed), step)
# that is ALSO composition-independent, so a sampled output replays from
# (seed, prompt) alone. Every knob is a traced per-row value, so one
# program serves any mix of them.


def _pick_token_row(temperature, top_p, top_k, logits, sub):
    """Per-row sampling where every knob is a TRACED scalar (so one decode
    program serves any per-row mix): temperature 0 selects greedy argmax,
    ``top_k`` 0 disables the rank filter, ``top_p`` 1.0 disables the nucleus
    filter. Differences from the static-knob :func:`_pick_tokens`: top-k is
    by rank (exactly k survivors; value ties at the k-th logit break by sort
    order instead of all surviving), and the sort always exists in the
    program — per-row knobs cannot statically elide it. The greedy branch is
    the same argmax, so a greedy row's TOKEN is unaffected by either. Its
    COST is spared only where the temperature is one scalar (the prefill
    programs: a real conditional); under ``vmap`` the ``cond`` becomes a
    select and every row pays for the sort, which is why the decode programs
    go through :func:`_pick_token_rows`."""

    def sample():
        l = logits / jnp.maximum(temperature, 1e-6)
        order = jnp.argsort(-l)  # stable: first max stays first
        srt = jnp.take_along_axis(l, order, -1)
        ranks = jnp.arange(l.shape[-1])
        srt = jnp.where(jnp.where(top_k > 0, ranks < top_k, True),
                        srt, -jnp.inf)
        probs = jax.nn.softmax(srt, axis=-1)
        keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p  # exclusive mass
        keep = keep.at[..., 0].set(True)  # top_p -> 0 must mean greedy
        srt = jnp.where(keep, srt, -jnp.inf)
        inv = jnp.argsort(order)
        return jax.random.categorical(
            sub, jnp.take_along_axis(srt, inv, -1)).astype(jnp.int32)

    return jax.lax.cond(
        temperature > 0.0, sample,
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))


def _row_key(seed, step):
    """The per-row sampling stream: fold the emitted-token index into the
    row's seed key. Depends only on (seed, step) — never on slot index or
    co-resident rows — so sampled replay is composition-independent."""
    return jax.random.fold_in(jax.random.key(seed), step)


def _pick_token_rows(temperature, top_p, top_k, logits, seeds, steps_done):
    """:func:`_pick_token_row` for every row of a decode batch — (B,) knobs,
    seeds and emitted-token counts, (B, V) logits — with the sort behind ONE
    conditional on a scalar: a batch with no sampled row takes the argmax
    and never sorts the vocabulary (on the serving cell the two argsorts
    were 16 of a decode step's 42 ms, for rows that were all greedy). A
    batch with any sampled row runs the per-row function on each row's
    :func:`_row_key` stream for all of its rows, its select included, so
    every row's token is what the per-row function gives it. Hand it
    temperature 0 for rows whose token is discarded (free, prefilling), or
    one of them switches the sort on for a batch of greedy rows."""
    return jax.lax.cond(
        jnp.any(temperature > 0.0),
        lambda: jax.vmap(_pick_token_row)(
            temperature, top_p, top_k, logits,
            jax.vmap(_row_key)(seeds, steps_done)),
        lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32))


# --------------------------------------------------------------------------
# Paged serving: the KV pool is a single device-resident page slab
# (num_pages, page_len, kv_heads, dh) per layer shared by EVERY bucket, and
# a row's cache is a host-side *block table* of page ids covering positions
# [0, W*page_len). Three programs compose it (serving/kvpool.py owns the
# host side — free lists, refcounts, copy-on-write prefix sharing):
#
#   lm_prefill_paged  one bounded CHUNK of a prompt (C tokens, C a multiple
#                     of page_len, chunk_start page-aligned): gathers the
#                     row's prefix context by block table, attends the chunk
#                     causally against it, and scatters the chunk's K/V into
#                     the C/page_len pages it covers. Resumable — a long
#                     prompt prefills across worker iterations, bounding how
#                     long any one iteration is away from decode.
#   lm_decode_paged   one token for every row of a call: per-row block-
#                     table gather of the paged context, the SAME
#                     _decode_step math as lm_generate (greedy stays
#                     bit-identical to it), and a scatter of the one cache
#                     entry each row wrote.
#   kv_page_copy      dst <- src for one page across all layers — the
#                     copy-on-write half of prefix sharing.
#
# Page 0 is the sacrificial dummy: block-table entries beyond a row's
# allocation (and whole tables of free/prefilling rows during decode) point
# at it, so out-of-extent gathers read garbage that masking discards and
# out-of-extent scatters scribble where nothing valid ever lives.


def init_kv_pages(params, num_pages: int, page_len: int, heads,
                  compute_dtype: str | None = None, window_pages: int = 0,
                  state_slots: int = 0):
    """Zeroed page slab: layer -> (k, v), each (num_pages, page_len,
    kv_heads, dh) in the compute dtype. One slab per engine — buckets share
    it; only block tables are bucket-shaped. Keep ``page_len`` a multiple
    of 8 (16 default) so pages stay sublane-aligned on TPU and the decode
    gather stays on the fast path (PAPERS.md 2202.05868: block geometry
    must track the MXU/lane grid). For a :class:`~.hybrid.ModelSpec` the
    sliding layers' slabs hold ``window_pages`` pages instead, and a layer
    with a state-space mixer has ``state_slots`` recurrent-state slots
    beside its pages (:func:`.hybrid.init_kv_pages`)."""
    if isinstance(heads, ModelSpec):
        from . import hybrid

        return hybrid.init_kv_pages(heads, num_pages, window_pages, page_len,
                                    compute_dtype, state_slots)
    if num_pages < 2:
        raise ValueError(f"num_pages must be >= 2 (page 0 is the dummy), "
                         f"got {num_pages}")
    if page_len < 1:
        raise ValueError(f"page_len must be >= 1, got {page_len}")
    d = params["emb"].shape[1]
    dh = d // heads
    kvh = params["l0"]["wk"].shape[1] // dh  # kv_heads <= heads under GQA
    dt = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype
    return {f"l{i}": tuple(jnp.zeros((num_pages, page_len, kvh, dh), dt)
                           for _ in range(2))
            for i in range(_n_layers(params))}


def lm_prefill_paged(params, pages, table, chunk, chunk_start, length,
                     heads: int, page_len: int, seed=0, temperature=0.0,
                     top_p=None, top_k=None,
                     compute_dtype: str | None = None,
                     moe: tuple | None = None):
    """One chunk of a paged prefill.

    ``pages`` is the pool slab (:func:`init_kv_pages`) — DONATED, replace
    your reference with the returned dict. ``table`` is this row's block
    table, (W_t,) int32 page ids covering positions ``[0, W_t*page_len)``
    in order (pad unallocated tail entries with the dummy page 0);
    ``chunk`` is (C,) int32 prompt tokens starting at absolute position
    ``chunk_start`` (pad past the prompt with zeros). STATIC contract the
    caller must honor: ``C % page_len == 0`` and ``chunk_start`` a multiple
    of ``page_len`` (the chunk then covers exactly ``C/page_len`` block-
    table slots — the scatter is page-exact and never touches a shared
    prefix page), and ``chunk_start/page_len + C/page_len <= W_t``.

    The chunk attends causally over the gathered prefix (pages written by
    earlier chunks — or by ANOTHER request, the copy-on-write prefix-share
    read path) plus itself, writes its K/V pages through the block table,
    and returns ``(pages, first)`` where ``first`` is the sampled first
    token — meaningful only on the final chunk (the one containing position
    ``length - 1``); earlier chunks return a garbage sample the scheduler
    ignores. One compile per (C, W_t) shape — ``chunk_start``, ``length``,
    the table, and every sampling knob are traced.

    With a :class:`~.hybrid.ModelSpec` for ``heads``, ``table`` is the row's
    ``(global table, window ring)`` and the result is
    :func:`.hybrid.prefill_paged`'s ``(pages, first, counts, logits)``."""
    if isinstance(heads, ModelSpec):
        from . import hybrid

        return hybrid.prefill_paged(
            params, pages, table, chunk, chunk_start, length, heads,
            page_len, seed=seed, temperature=temperature, top_p=top_p,
            top_k=top_k)
    return _lm_prefill_paged_jit(
        params, pages, jnp.asarray(table, jnp.int32),
        jnp.asarray(chunk, jnp.int32), jnp.asarray(chunk_start, jnp.int32),
        jnp.asarray(length, jnp.int32), jnp.asarray(seed, jnp.uint32),
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
        jnp.asarray(0 if top_k is None else top_k, jnp.int32),
        heads=heads, page_len=page_len, compute_dtype=compute_dtype, moe=moe)


@functools.partial(jax.jit, static_argnames=("heads", "page_len",
                                             "compute_dtype", "moe"),
                   donate_argnums=(1,))
def _lm_prefill_paged_jit(params, pages, table, chunk, chunk_start, length,
                          seed, temperature, top_p, top_k, heads: int,
                          page_len: int, compute_dtype, moe=None):
    C = chunk.shape[0]
    if C % page_len:
        raise ValueError(f"chunk width {C} must be a multiple of "
                         f"page_len {page_len}")
    cp = C // page_len
    Wt = table.shape[0]
    L = Wt * page_len
    cdtype = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype
    d = params["emb"].shape[1]
    dh = d // heads
    scale = 1.0 / math.sqrt(dh)  # multiply, exactly as _prefill_attn
    x = params["emb"][chunk].astype(cdtype)
    s_page = chunk_start // page_len
    cols = jnp.arange(C)
    tpos = jnp.arange(L)
    # gather EVERY layer's context up front and scatter every layer's new
    # pages at the END (not interleaved with the per-layer math), with an
    # optimization barrier pinning the gathers' output layout: without it
    # the attention einsum's preferred operand layout propagates THROUGH
    # the gather to the slab parameter and XLA copies (re-lays-out) every
    # (num_pages, ...) buffer per call — a cost scaling with the POOL, not
    # the chunk (measured ~2.5x per chunk on the bench pool; the barrier
    # moves the transpose onto the small gathered context instead)
    ctx = jax.lax.optimization_barrier(
        {name: tuple(t[table].reshape(L, t.shape[2], dh) for t in kv)
         for name, kv in pages.items()})
    new_kv = {}
    for i in range(_n_layers(params)):
        lp = params[f"l{i}"]
        kvh = lp["wk"].shape[1] // dh
        h = _rmsnorm(x, lp["ln1"])
        q = (h @ lp["wq"].astype(cdtype)).reshape(C, heads, dh)
        k = (h @ lp["wk"].astype(cdtype)).reshape(C, kvh, dh)
        v = (h @ lp["wv"].astype(cdtype)).reshape(C, kvh, dh)
        # splice the chunk's own K/V into the gathered context at its
        # absolute position (page-aligned, so the update never clamps);
        # positions past the causal frontier hold stale/garbage pages and
        # are masked below
        ctx_k, ctx_v = ctx[f"l{i}"]
        ctx_k = jax.lax.dynamic_update_slice(
            ctx_k, k.astype(ctx_k.dtype), (chunk_start, 0, 0))
        ctx_v = jax.lax.dynamic_update_slice(
            ctx_v, v.astype(ctx_v.dtype), (chunk_start, 0, 0))
        kk, vv = ctx_k, ctx_v
        if kvh != heads:  # GQA: broadcast to query heads, as in _block
            kk, vv = (jnp.repeat(t, heads // kvh, axis=1) for t in (kk, vv))
        s = jnp.einsum("phd,thd->hpt", q, kk,
                       preferred_element_type=jnp.float32) * scale
        live = tpos[None, None, :] <= (chunk_start + cols)[None, :, None]
        s = jnp.where(live, s, -1e30)
        o = jnp.einsum("hpt,thd->phd",
                       jax.nn.softmax(s, axis=-1).astype(cdtype), vv)
        x = x + o.reshape(C, d) @ lp["wo"].astype(cdtype)
        h = _rmsnorm(x, lp["ln2"])
        if "moe" in lp:
            from .moe import moe_ffn

            tk, cf, gs = moe if moe is not None else _MOE_DEFAULTS
            mo, _ = moe_ffn(lp["moe"], h, mesh=None, top_k=tk,
                            capacity_factor=cf, group_size=gs)
            x = x + mo
        else:
            x = x + (jax.nn.gelu(h @ lp["w1"].astype(cdtype))
                     @ lp["w2"].astype(cdtype))
        new_kv[f"l{i}"] = (k, v)
    # scatter the chunk's pages back: exactly the cp table slots the chunk
    # covers — a shared prefix page (always before chunk_start) is never
    # written, which is what makes read-sharing safe. The write is an
    # UNROLLED chain of single-page dynamic updates rather than one
    # vector-index scatter: XLA CPU expands the scatter form into a while
    # loop whose slab-sized carry COPIES the pool every chunk (a cost
    # scaling with the pool, not the chunk — measured ~2.5x per chunk on
    # the bench pool), while the DUS chain updates the donated slab in
    # place. cp is small and static, so the unroll is a handful of ops.
    new_pages = {}
    for name, (pk, pv) in pages.items():
        k, v = new_kv[name]
        kvh = pk.shape[2]
        pgk = k.astype(pk.dtype).reshape(cp, page_len, kvh, dh)
        pgv = v.astype(pv.dtype).reshape(cp, page_len, kvh, dh)
        for j in range(cp):
            pid = table[s_page + j]
            pk = jax.lax.dynamic_update_index_in_dim(pk, pgk[j], pid, 0)
            pv = jax.lax.dynamic_update_index_in_dim(pv, pgv[j], pid, 0)
        new_pages[name] = (pk, pv)
    xf = _rmsnorm(x, params["ln_f"])
    idx = jnp.clip(length - 1 - chunk_start, 0, C - 1)
    logits = _head_logits(xf[idx], params["emb"])
    first = _pick_token_row(temperature, top_p, top_k, logits,
                            _row_key(seed, 0))
    return new_pages, first


def resolve_decode_kernel(kernel: str | None = None) -> str:
    """Resolve a ``serve_decode_kernel`` setting to a concrete backend.

    ``None`` reads the config knob; ``'auto'`` picks ``'pallas'`` on real
    TPU (the fused kernel's Mosaic target) and ``'gather'`` elsewhere —
    interpret-mode Pallas is correct on CPU (the tests run it) but
    per-page-serialized, far too slow to serve with, while the gather
    path's scatter fix makes it the fast CPU formulation."""
    if kernel is None:
        from ..config import get_config

        kernel = get_config().serve_decode_kernel
    if kernel == "auto":
        kernel = "pallas" if jax.default_backend() == "tpu" else "gather"
    if kernel not in ("pallas", "gather"):
        raise ValueError(f"serve_decode_kernel must be 'auto', 'pallas' or "
                         f"'gather', got {kernel!r}")
    return kernel


def lm_decode_paged(params, pages, tables, positions, cur_tokens,
                    steps_done, seeds, temperature, top_p, top_k,
                    heads: int, page_len: int,
                    compute_dtype: str | None = None,
                    moe: tuple | None = None, kernel: str | None = None,
                    prev_tokens=None, prev_index=None):
    """One decode step for every row of a call over the paged pool.

    ``pages`` is the pool slab (DONATED). ``tables`` is (B, W) int32 block
    tables — pass an all-dummy (zero) row for every slot that is free or
    still prefilling: it computes a masked-harmless step against page 0
    whose outputs the scheduler ignores. ``cur_tokens`` is each row's last
    emitted token (the engine keeps the token stream host-side; the result
    is built from it). ``prev_tokens``/``prev_index`` feed a row its token
    from the DEVICE instead: where ``prev_index[b] >= 0`` row ``b``'s token
    is ``prev_tokens[prev_index[b]]`` (an earlier call's ``next_tokens``,
    which the host need not have seen: the engine dispatches a step before
    the last one has landed), elsewhere ``cur_tokens[b]``. The remaining
    per-row vectors, all (B,):
    ``positions`` the index of each row's last written token (the caller
    guarantees ``positions + 1 < W * page_len`` for live rows),
    ``steps_done`` the emitted-token count feeding the per-row sampling
    stream, ``seeds``/``temperature``/``top_p``/``top_k`` the per-row
    sampling knobs (0 temperature = greedy; ``top_p`` 1.0 / ``top_k`` 0 =
    off; hand a row whose token is discarded temperature 0).

    ``kernel`` selects the attention backend (default: the config's
    ``serve_decode_kernel``, resolved via :func:`resolve_decode_kernel`):

    - ``'gather'`` — the reference path: each row gathers its context by
      block table and runs the SAME :func:`_decode_step` math as
      :func:`lm_generate` (greedy rows stay bit-identical to it), then
      writes back the single cache entry it produced.
    - ``'pallas'`` — the fused :func:`~marlin_tpu.ops.paged_attention
      .paged_decode_attention` kernel attends over the page slab IN PLACE
      through the block table (no materialized context; requires
      ``page_len`` a multiple of 8). Greedy token streams match the gather
      path (logits agree to ~ulp — online softmax reassociates).

    Returns ``(pages, next_tokens)``. One compile per (B, W) table shape
    per backend. With a :class:`~.hybrid.ModelSpec` for ``heads``,
    ``tables`` is ``(global tables, window rings)`` and the result is
    :func:`.hybrid.decode_paged`'s ``(pages, next_tokens, counts,
    logits)``."""
    if isinstance(heads, ModelSpec):
        from . import hybrid

        return hybrid.decode_paged(
            params, pages, tables, positions, cur_tokens, steps_done, seeds,
            temperature, top_p, top_k, heads, page_len,
            resolve_decode_kernel(kernel), prev_tokens, prev_index)
    as_i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    return _lm_decode_paged_jit(
        params, pages, as_i32(tables), as_i32(positions),
        as_i32(cur_tokens), as_i32(steps_done),
        jnp.asarray(seeds, jnp.uint32),
        jnp.asarray(temperature, jnp.float32),
        jnp.asarray(top_p, jnp.float32), as_i32(top_k),
        heads=heads, page_len=page_len, compute_dtype=compute_dtype, moe=moe,
        kernel=resolve_decode_kernel(kernel),
        **_fed_tokens(prev_tokens, prev_index))


def _fed_tokens(prev_tokens, prev_index) -> dict:
    """The decode programs' two optional inputs as int32 arrays (both or
    neither: without them a program is traced without the select)."""
    if prev_tokens is None:
        return {}
    return {"prev_tokens": jnp.asarray(prev_tokens, jnp.int32),
            "prev_index": jnp.asarray(prev_index, jnp.int32)}


def _select_tokens(cur_tokens, prev_tokens, prev_index):
    """Each row's current token: ``prev_tokens[prev_index]`` where the index
    is not negative (a token the host has not seen), else ``cur_tokens``."""
    if prev_tokens is None:
        return cur_tokens
    return jnp.where(prev_index >= 0,
                     prev_tokens[jnp.maximum(prev_index, 0)], cur_tokens)


def feed_token(feed, index, token):
    """``feed`` (a decode call's ``next_tokens``, or zeros before the first
    call) with ``token`` written at ``index``: how a final prefill chunk's
    first token joins the tokens the next decode call is fed from the
    device (``prev_tokens``) without the host seeing it. All traced: ONE
    compiled program per feed width."""
    return _feed_token_jit(jnp.asarray(feed, jnp.int32),
                           jnp.asarray(index, jnp.int32),
                           jnp.asarray(token, jnp.int32))


@jax.jit
def _feed_token_jit(feed, index, token):
    return feed.at[index].set(token)


def _scatter_kv_entries(pk, pv, k_new, v_new, pids, off):
    """Write row b's new K/V cache entry to ``(pids[b], off[b])`` of the
    (donated) page slab as an UNROLLED chain of single-entry dynamic
    updates. The obvious vector-index form (``pk.at[pids, off].set(...)``)
    expands on XLA CPU into a while loop whose slab-sized carry COPIES the
    pool every step — the same pathology (and the same fix) as the prefill
    scatter above, but here it recurs EVERY decode step and was the whole
    measured −5±3% no-prefix paged tax. B is small and static, so the
    unroll is a handful of in-place updates. Dummy rows all target page 0
    offset 0; their duplicate writes are last-writer garbage in a page
    nothing valid ever reads (ordering is irrelevant — every write to a
    location nothing reads is equally garbage). ``k_new``/``v_new`` are (B,
    kvh, dh); an entry takes the shape of the slab's own page row: ``(kvh,
    dh)`` here, one row of ``kvh * dh`` in a spec model's slab
    (:func:`~marlin_tpu.models.hybrid.init_kv_pages`)."""
    B = pids.shape[0]
    entry = (1, 1) + pk.shape[2:]   # (kvh, dh), or one row of kvh * dh
    for b in range(B):
        idx = (pids[b], off[b]) + (0,) * (pk.ndim - 2)
        pk = jax.lax.dynamic_update_slice(pk, k_new[b].reshape(entry), idx)
        pv = jax.lax.dynamic_update_slice(pv, v_new[b].reshape(entry), idx)
    return pk, pv


def _decode_paged_pallas(params, pages, tables, pos, x, heads: int,
                         page_len: int, moe):
    """The fused-kernel decode body: batched projections, the new K/V entry
    written to the slab FIRST (so the kernel's length-masked read covers
    it, exactly as :func:`_decode_step` updates the cache before
    attending), then one :func:`paged_decode_attention` call per layer
    over the slab in place. Same layer math as :func:`_decode_step`, batch
    formulation."""
    from ..ops.paged_attention import paged_decode_attention

    B, W = tables.shape
    rows = jnp.arange(B)
    cd = x.dtype
    d = x.shape[-1]
    dh = d // heads
    pids = tables[rows, pos // page_len]
    off = pos % page_len
    lengths = pos + 1  # the just-written entry is live
    new_pages = {}
    for i in range(_n_layers(params)):
        lp = params[f"l{i}"]
        pk, pv = pages[f"l{i}"]
        kvh = pk.shape[2]
        h = _rmsnorm(x, lp["ln1"])
        q = (h @ lp["wq"].astype(cd)).reshape(B, kvh, heads // kvh, dh)
        k = (h @ lp["wk"].astype(cd)).reshape(B, kvh, dh)
        v = (h @ lp["wv"].astype(cd)).reshape(B, kvh, dh)
        pk, pv = _scatter_kv_entries(pk, pv, k.astype(pk.dtype),
                                     v.astype(pv.dtype), pids, off)
        o = paged_decode_attention(q, pk, pv, tables, lengths)
        x = x + o.reshape(B, d) @ lp["wo"].astype(cd)
        h = _rmsnorm(x, lp["ln2"])
        if "moe" in lp:
            from .moe import moe_decode_ffn

            x = x + jax.vmap(lambda hb, _lp=lp: moe_decode_ffn(
                _lp["moe"], hb, top_k=(moe or _MOE_DEFAULTS)[0]))(h)
        else:
            x = x + jax.nn.gelu(h @ lp["w1"].astype(cd)) @ lp["w2"].astype(cd)
        new_pages[f"l{i}"] = (pk, pv)
    x = _rmsnorm(x, params["ln_f"])
    return _head_logits(x, params["emb"]), new_pages


@functools.partial(jax.jit, static_argnames=("heads", "page_len",
                                             "compute_dtype", "moe",
                                             "kernel"),
                   donate_argnums=(1,))
def _lm_decode_paged_jit(params, pages, tables, positions, cur_tokens,
                         steps_done, seeds, temperature, top_p, top_k,
                         heads: int, page_len: int, compute_dtype,
                         moe=None, kernel: str = "gather",
                         prev_tokens=None, prev_index=None):
    B, W = tables.shape
    cur_tokens = _select_tokens(cur_tokens, prev_tokens, prev_index)
    L = W * page_len
    rows = jnp.arange(B)
    cdtype = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype
    # clamp so a mis-set position scribbles inside the gathered extent (its
    # page write then lands in a page the row owns — or the dummy) instead
    # of clipping out of bounds
    pos = jnp.minimum(positions, L - 1)
    x = params["emb"][cur_tokens].astype(cdtype)
    if kernel == "pallas":
        logits, new_pages = _decode_paged_pallas(
            params, pages, tables, pos, x, heads, page_len, moe)
        nxt = _pick_token_rows(temperature, top_p, top_k, logits, seeds,
                               steps_done)
        return new_pages, nxt
    # gather each row's context in block-table order: position t of the
    # gathered view IS absolute position t, so _decode_step's positional
    # masking applies unchanged — the decode math is literally
    # lm_generate's (bit-identity by construction, not by re-derivation)
    ctx = {name: tuple(t[tables].reshape(B, L, *t.shape[2:]) for t in kv)
           for name, kv in pages.items()}
    logits, new_ctx = jax.vmap(
        lambda xb, cb, pb: _decode_step(params, xb, cb, pb, heads, moe)
    )(x, ctx, pos)
    nxt = _pick_token_rows(temperature, top_p, top_k, logits, seeds,
                           steps_done)
    # write back the ONE cache entry each row produced — sliced at pos out
    # of the updated per-row context, which lets XLA fold the update-then-
    # slice into the entry itself instead of materializing a whole updated
    # context copy per layer
    pids = tables[rows, pos // page_len]
    off = pos % page_len
    new_pages = {}
    for name, (pk, pv) in pages.items():
        ck, cv = new_ctx[name]

        def entry(c, p):
            return jax.lax.dynamic_index_in_dim(c, p, 0, keepdims=False)

        new_pages[name] = _scatter_kv_entries(
            pk, pv, jax.vmap(entry)(ck, pos).astype(pk.dtype),
            jax.vmap(entry)(cv, pos).astype(pv.dtype), pids, off)
    return new_pages, nxt


def kv_page_copy(pages, src, dst):
    """Copy page ``src`` onto page ``dst`` across every layer's K and V —
    the device half of copy-on-write prefix sharing (``pages`` DONATED;
    ``src``/``dst`` traced, so every copy shares ONE compiled program per
    slab shape)."""
    return _kv_page_copy_jit(pages, jnp.asarray(src, jnp.int32),
                             jnp.asarray(dst, jnp.int32))


@functools.partial(jax.jit, donate_argnums=(0,))
def _kv_page_copy_jit(pages, src, dst):
    return {name: tuple(t.at[dst].set(t[src]) for t in kv)
            for name, kv in pages.items()}


# forward the private jit cache-size probe through the un-jitted shims (the
# no-recompile tests/benches read it)
for _pub, _jit in ((lm_generate, _lm_generate_jit),
                   (lm_generate_batch, _lm_generate_batch_jit),
                   (lm_prefill_paged, _lm_prefill_paged_jit),
                   (lm_decode_paged, _lm_decode_paged_jit),
                   (kv_page_copy, _kv_page_copy_jit)):
    _pub._cache_size = _jit._cache_size
del _pub, _jit


@dataclasses.dataclass
class TransformerLM:
    """Trainer facade in the style of :class:`marlin_tpu.ml.NeuralNetwork`."""

    vocab: int = 256
    d_model: int = 64
    heads: int = 4
    layers: int = 2
    d_ff: int | None = None
    learning_rate: float = 3e-3
    seed: int = 0
    attn: str = "ring"  # "ring" | "ring_flash" | "ring_xla" | "ulysses"
    remat: bool = False
    precision: str = "high"  # "default" = bf16 MXU operands in attention
    loss_chunk: int | None = None  # scan the LM head over chunks (HBM knob)
    # "bfloat16" halves activation HBM (params/Adam stay f32 — true mixed
    # precision); with remat+loss_chunk this is what fits 1M tokens on one
    # 16 GB v5e (AOT_MEMORY.json)
    compute_dtype: str | None = None
    # scan the FFN over this many tokens at a time: caps the (seq, d_ff)
    # GELU intermediate at (chunk, d_ff) — worth ~GiBs at 1M+ tokens, more
    # at larger d_ff
    mlp_chunk: int | None = None
    # park the remat residual checkpoints (L·S·d, the only forward state
    # remat keeps) in pinned host RAM between forward and backward. The knob
    # for residual-DOMINATED shapes (many layers x large d_model): the
    # compiler confirms the checkpoints move to host temps, but the
    # scan-over-layers formulation it requires costs some device memory
    # back, so at small L·d it is net-neutral (AOT_MEMORY.json
    # lct_long_bf16_offload). Requires remat=True.
    offload_residuals: bool = False
    # grouped-query attention: heads//kv_heads query heads share one K/V
    # head, dividing the decode KV cache (and the K/V projections) by the
    # group factor — the serving memory lever. None = standard MHA. Every
    # downstream consumer derives it from the parameter shapes.
    kv_heads: int | None = None
    # mixture-of-experts FFN (models/moe.py): n_experts switches every
    # moe_every-th layer's FFN to that many experts, sharded over the mesh
    # rows axis at training (expert parallelism — the all_to_all token
    # shuffle comes from sharding constraints). top_k/capacity/group are the
    # GShard routing knobs; aux_weight scales the Switch load-balance term.
    n_experts: int | None = None
    moe_every: int = 1
    moe_top_k: int = _MOE_DEFAULTS[0]
    moe_capacity_factor: float = _MOE_DEFAULTS[1]
    moe_group: int = _MOE_DEFAULTS[2]
    moe_aux_weight: float = 1e-2

    def _moe(self) -> tuple | None:
        if self.n_experts is None:
            return None
        return (self.moe_top_k, self.moe_capacity_factor, self.moe_group)

    def init_params(self, dtype=jnp.float32) -> dict:
        return init_transformer(jax.random.key(self.seed), self.vocab,
                                self.d_model, self.heads, self.layers,
                                self.d_ff, dtype, self.kv_heads,
                                self.n_experts, self.moe_every)

    def train(self, tokens, steps: int = 20, mesh=None, params=None,
              checkpoint_dir: str | None = None, checkpoint_every: int = 0,
              log_every: int = 0):
        """Train on one long token stream (context-parallel regime). Returns
        (params, losses)."""
        import optax

        from ..io.checkpoint import save_checkpoint
        from ..mesh import default_mesh

        mesh = mesh or default_mesh()
        tokens = jnp.asarray(np.asarray(tokens), jnp.int32)
        params = params if params is not None else self.init_params()
        if self.n_experts is not None:
            # expert parallelism by placement: shard the expert tensors over
            # the mesh rows axis; propagation shards the expert compute
            from .moe import shard_moe_params

            params = shard_moe_params(params, mesh)
        opt_state = optax.adam(self.learning_rate).init(params)

        losses = []
        for it in range(steps):
            params, opt_state, loss = lm_train_step(
                params, opt_state, tokens, mesh, self.heads, self.attn,
                self.remat, self.precision, self.learning_rate,
                self.loss_chunk, self.compute_dtype, self.mlp_chunk,
                self.offload_residuals, self._moe(), self.moe_aux_weight,
            )
            losses.append(float(loss))
            if log_every and (it + 1) % log_every == 0:
                print(f"step {it + 1}: loss {losses[-1]:.4f}")
            if checkpoint_dir and checkpoint_every and (it + 1) % checkpoint_every == 0:
                save_checkpoint({"params": params, "opt_state": opt_state},
                                checkpoint_dir, it + 1)
        return params, losses

    def generate(self, params, prompt, steps: int = 32,
                 max_len: int | None = None, temperature=0.0,
                 top_p=None, top_k: int | None = None,
                 seed: int | None = None):
        """Sample ``steps`` tokens continuing ``prompt`` with the params
        returned by :meth:`train` (see :func:`lm_generate`; ``temperature``
        and ``top_p`` are traced — sweeping them reuses one compiled
        program)."""
        key = jax.random.key(self.seed if seed is None else seed)
        if max_len is None:
            max_len = len(prompt) + steps
        return lm_generate(params, prompt, key, heads=self.heads,
                           max_len=max_len, steps=steps,
                           temperature=temperature, top_p=top_p, top_k=top_k,
                           compute_dtype=self.compute_dtype, moe=self._moe())

    def generate_batch(self, params, prompts, steps: int = 32,
                       max_len: int | None = None, temperature=0.0,
                       top_p=None, top_k: int | None = None,
                       seed: int | None = None):
        """Batched decode over a LIST of prompts (ragged lengths welcome):
        pads them to a common length and runs :func:`lm_generate_batch`.
        Returns a list of 1-D arrays, each ``prompt + steps`` tokens."""
        lengths = np.array([len(p) for p in prompts], np.int32)
        P = int(lengths.max())
        padded = np.zeros((len(prompts), P), np.int32)
        for i, p in enumerate(prompts):
            padded[i, : len(p)] = np.asarray(p)
        if max_len is None:
            max_len = P + steps
        key = jax.random.key(self.seed if seed is None else seed)
        out = lm_generate_batch(params, padded, lengths, key,
                                heads=self.heads, max_len=max_len,
                                steps=steps, temperature=temperature,
                                top_p=top_p, top_k=top_k,
                                compute_dtype=self.compute_dtype,
                                moe=self._moe())
        out = np.asarray(out)
        return [out[i, : lengths[i] + steps] for i in range(len(prompts))]
