"""AOT compile-only TPU evidence (utils/aot.py): the Pallas kernels must pass
the REAL Mosaic compiler for v5e — interpret-mode correctness on the CPU mesh
(the rest of the suite) says nothing about what Mosaic accepts — and the
flash-backward memory claims must hold in the TPU lowering's own accounting,
not a CPU-lowering proxy.

These tests need libtpu (the compiler) but no chip; they skip cleanly where
no topology can be described.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import marlin_tpu as mt


# The topology is described INSIDE fixtures, never while a module is imported:
# describing it loads libtpu, which one process at a time may hold, and every
# xdist worker imports every test file. Here only the worker that is handed
# this file loads it, and every worker collects the same tests.
@pytest.fixture(scope="module")
def topo():
    """The described (not attached) ``v5e:2x2``; skips when it cannot be."""
    from marlin_tpu.utils.aot import tpu_topology

    try:
        return tpu_topology("v5e:2x2")
    except RuntimeError as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The canonical single-device AOT placement (replicated on one topo
    chip) — shared by every single-chip compile test."""
    mesh = Mesh(np.array([topo.devices[0]]).reshape(1, 1), ("a", "b"))
    return NamedSharding(mesh, P())


@pytest.fixture(scope="module")
def topo_mesh(topo):
    """``topo_mesh(axis_names, shape)``: a Mesh over the described chips."""
    def make(axis_names, shape):
        n = int(np.prod(shape))
        return Mesh(np.asarray(topo.devices)[:n].reshape(shape), axis_names)

    return make


def _abstract_decode_args(lm, rep):
    """Replicated abstract (params, key, temperature) for the decode AOT
    compiles — the boilerplate every decode-path test shares (a
    trace-signature change edits ONE place)."""
    params = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=rep),
        jax.eval_shape(lm.init_params))
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    temp = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    return params, key, temp


def _compile1(fn, arg_shapes, rep):
    """AOT-compile ``fn`` for one topology device, fully replicated."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in arg_shapes]
    return jax.jit(fn, in_shardings=rep, out_shardings=rep) \
        .trace(*args).lower().compile()


def test_flash_forward_mosaic_compiles(one_chip):
    from marlin_tpu.ops.flash_attention import flash_attention_panel

    S, D, B = 2048, 128, 1024
    c = _compile1(
        lambda q, k, v, m, l, acc: flash_attention_panel(
            q, k, v, m, l, acc, 0, 0, S, causal=True, scale=0.125,
            bq=B, bkv=B, interpret=False),
        [(S, D), (S, D), (S, D), (S,), (S,), (S, D)], one_chip)
    assert c.memory_analysis().temp_size_in_bytes == 0  # streams via VMEM


def test_flash_backward_mosaic_compiles(one_chip):
    from marlin_tpu.ops.flash_attention import flash_attention_panel_bwd

    S, D, B = 2048, 128, 1024
    c = _compile1(
        lambda q, k, v, do, lse, delta: flash_attention_panel_bwd(
            q, k, v, do, lse, delta, 0, 0, S, causal=True, scale=0.125,
            bq=B, bkv=B, interpret=False),
        [(S, D), (S, D), (S, D), (S, D), (S,), (S,)], one_chip)
    assert c.memory_analysis().temp_size_in_bytes == 0


def test_bsr_manual_dma_mosaic_compiles(one_chip):
    """The double-buffered make_async_copy kernel with pl.ANY HBM refs and
    scalar-prefetch-driven index maps — exactly the shape of code Mosaic
    rejects in surprising ways (round-2/3 verdicts); prove it compiles."""
    from marlin_tpu.ops.sparse_bsr import BsrMatrix, bsr_from_coo, \
        bsr_spmm_pallas

    rng = np.random.default_rng(0)
    M = N = K = 1024
    bs, nb = 128, 12
    flat = rng.choice(M // bs * (K // bs), nb, replace=False)
    ri, ci = np.divmod(flat, K // bs)
    coo_r = np.concatenate([(r * bs + np.arange(bs)).repeat(bs) for r in ri])
    coo_c = np.concatenate([np.tile(c * bs + np.arange(bs), bs) for c in ci])
    coo_v = rng.random(nb * bs * bs).astype(np.float32)
    bsr = bsr_from_coo(coo_r, coo_c, coo_v, (M, K), block_size=bs)

    def spmm(blocks, b):
        m = BsrMatrix(blocks=blocks, block_rows=bsr.block_rows,
                      block_cols=bsr.block_cols, shape=bsr.shape,
                      block_size=bsr.block_size)
        return bsr_spmm_pallas(m, b, interpret=False)

    _compile1(spmm, [tuple(bsr.blocks.shape), (K, N)], one_chip)


def _ring_grad_memory(mesh, seq, backend):
    from marlin_tpu.parallel.ring_attention import ring_attention

    s = NamedSharding(mesh, P("rows", None))
    with mt.config_context(pallas_interpret=False):
        g = jax.jit(
            jax.grad(lambda q, k, v: jnp.sum(ring_attention(
                q, k, v, mesh, causal=True, backend=backend)),
                argnums=(0, 1, 2)),
            in_shardings=(s, s, s), out_shardings=(s, s, s))
        a = jax.ShapeDtypeStruct((seq, 128), jnp.float32)
        return g.trace(a, a, a).lower().compile().memory_analysis()


def test_flash_backward_memory_flat_on_tpu(topo_mesh):
    """TPU-lowering accounting of the training backward (the CPU-proxy
    version lives in test_ring_attention.py): the flash path holds ZERO HBM
    temps at any length — score tiles live and die in VMEM — and its peak
    memory is linear in seq; the autodiff-through-XLA backward it replaced
    pays quadratic-plus temp growth at the same shapes."""
    mesh = topo_mesh(("rows",), (4,))
    f8 = _ring_grad_memory(mesh, 8192, "flash")
    f16 = _ring_grad_memory(mesh, 16384, "flash")
    assert f8.temp_size_in_bytes == 0 and f16.temp_size_in_bytes == 0
    assert f16.peak_memory_in_bytes < 2.5 * f8.peak_memory_in_bytes

    x16 = _ring_grad_memory(mesh, 16384, "xla")
    # the replaced formulation's residuals: ~830 MB of temps at 16k vs 0
    assert x16.temp_size_in_bytes > 100 * 1024 * 1024
    assert x16.peak_memory_in_bytes > 10 * f16.peak_memory_in_bytes


def test_distributed_engines_compile_for_8chip_v5e(topo):
    """The flagship distributed programs — gspmd, ring (ppermute pipeline),
    3-D RMM (psum over k), ulysses (all_to_all re-shard) — AOT-compiled for
    a real 8-chip v5e topology: the collective schedules the CPU mesh proves
    numerically are accepted and scheduled by the TPU compiler over ICI."""
    from marlin_tpu.parallel.matmul import gspmd_matmul, rmm_matmul
    from marlin_tpu.parallel.ring import ring_matmul
    from marlin_tpu.parallel.ulysses import ulysses_attention
    from marlin_tpu.utils.aot import tpu_topology

    # `topo` has loaded libtpu (or skipped); the 8-chip description rides it
    devs = list(np.asarray(tpu_topology("v5e:2x4").devices).ravel())
    mesh2d = Mesh(np.array(devs).reshape(2, 4), ("rows", "cols"))
    row = NamedSharding(mesh2d, P("rows", None))
    blk = NamedSharding(mesh2d, P("rows", "cols"))
    a = jax.ShapeDtypeStruct((512, 512), jnp.float32, sharding=row)

    c = jax.jit(lambda x, y: gspmd_matmul(x, y, blk)) \
        .trace(a, a).lower().compile()
    assert c.memory_analysis().peak_memory_in_bytes > 0

    jax.jit(lambda x, y: ring_matmul(x, y, mesh2d)) \
        .trace(a, a).lower().compile()

    jax.jit(lambda x, y: rmm_matmul(x, y, split=(2, 2, 2), devices=devs)) \
        .trace(a, a).lower().compile()

    meshr = Mesh(np.array(devs).reshape(8), ("rows",))
    h = jax.ShapeDtypeStruct((8, 1024, 128), jnp.float32,
                             sharding=NamedSharding(meshr, P(None, "rows", None)))
    with mt.config_context(pallas_interpret=False):
        jax.jit(lambda q, k, v: ulysses_attention(q, k, v, meshr, causal=True)) \
            .trace(h, h, h).lower().compile()


def test_decode_path_compiles_for_v5e(one_chip):
    """lm_generate (batched prefill + scan decode + traced temperature)
    AOT-compiled for a v5e device — the decode bench's program is proven
    before it ever reaches the chip."""
    from marlin_tpu.models.transformer import (TransformerLM,
                                               _lm_generate_batch_jit,
                                               _lm_generate_jit)

    lm = TransformerLM(vocab=4096, d_model=512, heads=8, layers=4, seed=0)
    rep = one_chip
    params, key, temp = _abstract_decode_args(lm, rep)
    prompt = jax.ShapeDtypeStruct((512,), jnp.int32, sharding=rep)
    c = _lm_generate_jit.trace(params, prompt, key, heads=8, max_len=832,
                               steps=320, temperature=temp,
                               compute_dtype=None, top_p=temp,
                               use_top_p=True, top_k=40).lower().compile()
    assert c.memory_analysis().peak_memory_in_bytes < 2 * 1024**3

    # the batched serving form: 8 ragged rows decode together
    prompts = jax.ShapeDtypeStruct((8, 512), jnp.int32, sharding=rep)
    lengths = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=rep)
    cb = _lm_generate_batch_jit.trace(
        params, prompts, lengths, key, heads=8, max_len=576, steps=64,
        temperature=temp, compute_dtype=None, top_p=temp, use_top_p=True,
        top_k=40).lower().compile()
    assert cb.memory_analysis().peak_memory_in_bytes < 4 * 1024**3


def test_pallas_matmul_and_masked_fill_mosaic_compile(one_chip):
    """The remaining two Pallas kernels (tiled MXU matmul, fused pad-mask)
    through real Mosaic — completing 'every Pallas kernel is AOT-proven'."""
    from marlin_tpu.ops.pallas_kernels import masked_fill, pallas_matmul

    rep = one_chip
    with mt.config_context(pallas_interpret=False):
        a = jax.ShapeDtypeStruct((512, 384), jnp.float32)
        b = jax.ShapeDtypeStruct((384, 256), jnp.float32)
        jax.jit(lambda a, b: pallas_matmul(a, b), in_shardings=rep,
                out_shardings=rep).trace(a, b).lower().compile()
        x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
        jax.jit(lambda x: masked_fill(x, 200, 190), in_shardings=rep,
                out_shardings=rep).trace(x).lower().compile()


def test_flash_prefill_memory_linear_on_tpu(one_chip):
    """Decode prefill past _PREFILL_FLASH_MIN runs the flash kernel, so the
    prompt's score memory never materializes: TPU-compiler peak for the whole
    lm_generate program must grow ~linearly from 8k to 16k prompts (the dense
    path it replaced held heads x P² f32 scores per layer — 2.1 -> 8.6 GiB
    quadratic growth at these shapes; ADVICE r4 / round-4 verdict #3)."""
    from marlin_tpu.models.transformer import (TransformerLM,
                                               _lm_generate_jit)

    lm = TransformerLM(vocab=4096, d_model=512, heads=8, layers=4, seed=0)
    rep = one_chip
    params, key, temp = _abstract_decode_args(lm, rep)

    def peak(plen):
        prompt = jax.ShapeDtypeStruct((plen,), jnp.int32, sharding=rep)
        with mt.config_context(pallas_interpret=False):
            c = _lm_generate_jit.trace(
                params, prompt, key, heads=8, max_len=plen + 16, steps=16,
                temperature=temp, compute_dtype=None, top_p=temp,
                use_top_p=False, top_k=None).lower().compile()
        return c.memory_analysis().peak_memory_in_bytes

    p8, p16 = peak(8192), peak(16384)
    assert p16 < 2.6 * p8, (p8, p16)
    # and nowhere near the dense path's 8.6 GiB of scores
    assert p16 < 2 * 1024**3, p16


def test_plan_context_real_compiles(topo):
    """plan_context against the real compiler: a tiny model at 32k tokens
    fits a generous budget as-configured, and a deliberately starved budget
    forces knob escalation whose chosen rung really fits (every number here
    is the TPU compiler's own accounting, not a heuristic)."""
    from marlin_tpu.models import TransformerLM, plan_context

    lm = TransformerLM(vocab=256, d_model=64, heads=2, layers=2,
                       attn="ring_flash")
    seq = 32768
    generous = plan_context(seq, lm, hbm_budget=15 * 1024**3)
    assert generous.fits and generous.knobs == {}

    starved = plan_context(seq, lm, hbm_budget=generous.peak_bytes - 1)
    assert starved.fits, starved.describe()
    assert starved.knobs  # at least one knob escalated
    assert starved.peak_bytes < generous.peak_bytes


def test_2m_tokens_single_chip_and_host_offload(topo_mesh):
    """The single-chip context cliff (r4 verdict #5), compiler-verified:

    1. 2M bf16 tokens — a 17-GiB compiler REJECTION before the exact-packed
       m/l kernel layout — now fit one v5e under *usable* HBM with the
       on-device knobs alone (remat + loss_chunk + mlp_chunk + bf16).
    2. offload_residuals genuinely moves the remat checkpoints off the
       device: ~2 GiB of host temps appear in the compiler's host-memory
       accounting and the device program still compiles. (At THIS config it
       is net-neutral — the scan formulation costs about what the offload
       saves — so it is the knob for residual-dominated shapes, more
       layers x d_model, not the default.)"""
    from marlin_tpu.models.planner import _compiled_peak, usable_hbm_bytes
    from marlin_tpu.models.transformer import TransformerLM

    mesh = topo_mesh(("rows",), (1,))
    lm = TransformerLM(vocab=512, d_model=256, heads=2, layers=2,
                       attn="ring_flash", remat=True, loss_chunk=16384,
                       compute_dtype="bfloat16", mlp_chunk=16384)
    peak, note = _compiled_peak(lm, 2097152, mesh)
    assert peak is not None, note
    assert peak <= usable_hbm_bytes(), (peak, usable_hbm_bytes())

    import dataclasses

    from marlin_tpu.utils.aot import trace_lm_train_step

    lm_off = dataclasses.replace(lm, offload_residuals=True)
    with mt.config_context(pallas_interpret=False):
        c = trace_lm_train_step(lm_off, 2097152, mesh).lower().compile()
    ma = c.memory_analysis()
    # the residuals (2 layers x 2M x 256 x bf16 = 2 GiB) live on the host
    assert ma.host_temp_size_in_bytes >= 2 * 1024**3
    assert ma.peak_memory_in_bytes < 16 * 1024**3


def test_plan_context_multichip(topo):
    """chips=4 certifies the SAME sharded ring program per chip: the 4M-token
    bf16 deployment the docs claim (remat + loss_chunk + bf16, AOT_MEMORY's
    lct_long_4chip row — NOT mlp_chunk, which measures ~1 GiB WORSE per chip
    in the sharded program; nonmonotonic knob interactions across topologies
    are exactly why the planner measures instead of assuming) compiles within
    per-chip usable HBM."""
    from marlin_tpu.models import TransformerLM, plan_context

    lm = TransformerLM(vocab=512, d_model=256, heads=2, layers=2,
                       attn="ring_flash", remat=True, loss_chunk=16384,
                       compute_dtype="bfloat16")
    plan = plan_context(4 * 1048576, lm, chips=4)
    assert plan.fits, plan.describe()
    assert plan.knobs == {}, plan.knobs  # fits as-documented, no escalation


def test_batched_long_prompt_decode_compiles(one_chip):
    """lm_generate_batch with prompts past _PREFILL_FLASH_MIN: the flash
    prefill kernel under NESTED vmap (batch x heads) must fold into the
    Mosaic grid and compile — the long-document serving shape."""
    from marlin_tpu.models.transformer import (TransformerLM,
                                               _lm_generate_batch_jit)

    lm = TransformerLM(vocab=4096, d_model=512, heads=8, layers=4, seed=0)
    rep = one_chip
    params, key, temp = _abstract_decode_args(lm, rep)
    prompts = jax.ShapeDtypeStruct((4, 4096), jnp.int32, sharding=rep)
    lengths = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=rep)
    with mt.config_context(pallas_interpret=False):
        c = _lm_generate_batch_jit.trace(
            params, prompts, lengths, key, heads=8, max_len=4160, steps=64,
            temperature=temp, compute_dtype=None, top_p=temp,
            use_top_p=False, top_k=None).lower().compile()
    assert c.memory_analysis().peak_memory_in_bytes < 2 * 1024**3


def test_gqa_decode_compiles_for_v5e(one_chip):
    """The grouped-query decode program (kv_heads=2 of 8: grouped einsums,
    quarter-width caches) compiles for v5e and its peak sits measurably
    below the full-MHA decode program at the same shape — the cache
    reduction is visible in the compiler's own accounting."""
    from marlin_tpu.models.transformer import TransformerLM, _lm_generate_jit

    rep = one_chip

    def peak(kvh):
        lm = TransformerLM(vocab=4096, d_model=512, heads=8, layers=4,
                           seed=0, kv_heads=kvh)
        params, key, temp = _abstract_decode_args(lm, rep)
        prompt = jax.ShapeDtypeStruct((512,), jnp.int32, sharding=rep)
        c = _lm_generate_jit.trace(
            params, prompt, key, heads=8, max_len=8192, steps=64,
            temperature=temp, compute_dtype=None, top_p=temp,
            use_top_p=False, top_k=None).lower().compile()
        return c.memory_analysis().peak_memory_in_bytes

    full, grouped = peak(None), peak(2)
    assert grouped < full, (grouped, full)
    # caches: 4 layers x 2 tensors x 8192 x 8 heads x dh=64 x f32 = 128 MB
    # total at full width; kv_heads=2 keeps a quarter -> ~96 MB reclaimed
    # (measured 102 MB of a 227 MB full-decode peak)
    assert full - grouped > 90 * 1024 * 1024, (grouped, full)


def test_moe_train_step_compiles_for_v5e(topo_mesh):
    """The MoE LM train step (grouped GShard routing + Switch aux in the
    loss) through the REAL TPU compiler, single chip — top_k/cumsum/one_hot
    dispatch einsums and the scan-over-groups must all lower."""
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.utils.aot import trace_lm_train_step

    mesh = topo_mesh(("rows", "cols"), (1, 1))
    lm = TransformerLM(vocab=512, d_model=256, heads=2, layers=2, remat=True,
                       loss_chunk=2048, n_experts=8, moe_group=2048)
    with mt.config_context(pallas_interpret=False):
        c = trace_lm_train_step(lm, 32768, mesh).lower().compile()
    peak = c.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < 16 * 1024 ** 3, peak


def test_moe_expert_parallel_compiles_for_4chip_v5e(topo_mesh):
    """Expert parallelism for a real 4-chip v5e: expert params sharded over
    the rows axis (the placement idiom), the compiler must accept and
    schedule the token-shuffle collectives its propagation inserts."""
    from marlin_tpu.models.moe import init_moe, moe_ffn

    mesh = topo_mesh(("rows", "cols"), (4, 1))
    mp = jax.eval_shape(lambda: init_moe(jax.random.key(0), 256, 1024, 8))
    exp = NamedSharding(mesh, P("rows", None, None))
    rep = NamedSharding(mesh, P())
    mp = {
        "wg": jax.ShapeDtypeStruct(mp["wg"].shape, mp["wg"].dtype,
                                   sharding=rep),
        "w1": jax.ShapeDtypeStruct(mp["w1"].shape, mp["w1"].dtype,
                                   sharding=exp),
        "w2": jax.ShapeDtypeStruct(mp["w2"].shape, mp["w2"].dtype,
                                   sharding=exp),
    }
    x = jax.ShapeDtypeStruct((16384, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P("rows", None)))
    c = jax.jit(lambda m, xx: moe_ffn(m, xx, mesh=mesh, top_k=2,
                                      group_size=4096)) \
        .trace(mp, x).lower().compile()
    assert c.memory_analysis().peak_memory_in_bytes > 0


def test_pipeline_compiles_for_4chip_v5e(topo_mesh):
    """The GPipe schedule (shard_map + ppermute hops + masked psum collect)
    through the TPU compiler for a real 4-chip topology."""
    from marlin_tpu.parallel.pipeline import pipeline_apply

    mesh = topo_mesh(("rows", "cols"), (4, 1))
    stage = NamedSharding(mesh, P("rows", None, None))
    params = {"w": jax.ShapeDtypeStruct((4, 512, 512), jnp.float32,
                                        sharding=stage)}
    x = jax.ShapeDtypeStruct((64, 512), jnp.float32,
                             sharding=NamedSharding(mesh, P()))
    c = jax.jit(lambda p, xx: pipeline_apply(
        p, lambda ps, xb: jnp.tanh(xb @ ps["w"]), xx, mesh, microbatch=8)) \
        .trace(params, x).lower().compile()
    assert c.memory_analysis().peak_memory_in_bytes > 0


def test_plan_context_moe_model(topo):
    """The planner handles MoE models end-to-end: the traced step carries
    the routing + aux and the expert tensors get their runtime EP sharding,
    so the compiler accounting the plan is built from matches the deployed
    program."""
    from marlin_tpu.models import TransformerLM, plan_context

    lm = TransformerLM(vocab=256, d_model=64, heads=2, layers=2,
                       attn="ring_flash", n_experts=4, moe_group=2048)
    plan = plan_context(16384, lm, hbm_budget=15 * 1024 ** 3)
    assert plan.fits and plan.peak_bytes > 0


def test_pipeline_tensor_parallel_composition_compiles(topo_mesh):
    """pp x tp on one mesh: pipeline stages over "rows" whose stage_fn is
    itself tensor-parallel over "cols" (column-sharded w0, row-sharded w1;
    pipeline_apply manualizes only the pipeline axis, so "cols" stays Auto
    and GSPMD shards the stage matmuls). Certified two ways: the TPU
    compiler accepts the composed program, AND the per-device argument
    footprint of the cols-sharded weights is ~half the replicated compile's
    — the tensor sharding genuinely survives into the pipeline (a
    fully-manual shard_map would all-gather it away at the boundary)."""
    from marlin_tpu.parallel.pipeline import pipeline_apply

    mesh = topo_mesh(("rows", "cols"), (2, 2))
    stage = NamedSharding(mesh, P("rows", None, None))
    col = NamedSharding(mesh, P("rows", None, "cols"))
    roww = NamedSharding(mesh, P("rows", "cols", None))
    x = jax.ShapeDtypeStruct((16, 256), jnp.float32,
                             sharding=NamedSharding(mesh, P()))

    def stage_fn(p, xb):
        h = jax.nn.relu(xb @ p["w0"])
        return jnp.tanh(h @ p["w1"] + p["b"])

    def compiled(w0_sh, w1_sh):
        params = {
            "w0": jax.ShapeDtypeStruct((2, 256, 512), jnp.float32,
                                       sharding=w0_sh),
            "w1": jax.ShapeDtypeStruct((2, 512, 256), jnp.float32,
                                       sharding=w1_sh),
            "b": jax.ShapeDtypeStruct(
                (2, 256), jnp.float32,
                sharding=NamedSharding(mesh, P("rows", None))),
        }
        return jax.jit(lambda p, xx: pipeline_apply(
            p, stage_fn, xx, mesh, microbatch=4)) \
            .trace(params, x).lower().compile()

    tp = compiled(col, roww).memory_analysis()
    rep = compiled(stage, stage).memory_analysis()
    assert tp.peak_memory_in_bytes > 0
    assert tp.argument_size_in_bytes < 0.75 * rep.argument_size_in_bytes, (
        tp.argument_size_in_bytes, rep.argument_size_in_bytes)


def test_pp_lm_train_step_compiles_for_4chip_v5e(topo_mesh):
    """The pipeline-parallel LM train step (4 stages of 1 block each,
    batched causal attention inside stages, Adam over stage + outer params)
    through the TPU compiler for a real 4-chip topology."""
    import optax

    from marlin_tpu.models.pipeline_lm import pp_lm_train_step, pp_stage_params
    from marlin_tpu.models.transformer import init_transformer

    mesh = topo_mesh(("rows", "cols"), (4, 1))
    params = jax.eval_shape(
        lambda: init_transformer(jax.random.key(0), 256, 128, 2, 4))
    rep = NamedSharding(mesh, P())

    def absify(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                              sharding=s), tree, shardings)

    # build the REAL stage split abstractly: eval_shape pp_stage_params to
    # get shapes, then shard the stage axis like the runtime does
    sp_shape, outer_shape = jax.eval_shape(
        lambda p: pp_stage_params(p, mesh), params)
    stage_sh = jax.tree.map(
        lambda x: NamedSharding(mesh, P("rows", *(None,) * (x.ndim - 1))),
        sp_shape)
    sp = absify(sp_shape, stage_sh)
    outer = absify(outer_shape, jax.tree.map(lambda _: rep, outer_shape))
    opt_shape = jax.eval_shape(optax.adam(1e-3).init, (sp_shape, outer_shape))
    opt = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=rep),
        opt_shape)
    toks = jax.ShapeDtypeStruct((8, 129), jnp.int32, sharding=rep)
    with mt.config_context(pallas_interpret=False):
        c = pp_lm_train_step.trace(sp, outer, opt, toks, mesh, heads=2,
                                   microbatch=2, lr=1e-3).lower().compile()
    assert c.memory_analysis().peak_memory_in_bytes > 0


# ---------------------------------------------------------------------------
# chip_smoke.py's programs at chip_smoke.py's widths: the d512/h8/L4/v4096 LM
# under ServeEngine's default geometry (buckets (64,32),(256,64), max_batch
# 8, page_len 16, auto-sized pool), and the 20000² rmm multiply on a 2x2
# mesh. Kernel selection is passed in the test (interpret=False /
# kernel="pallas"): jax.default_backend() is the CPU here.

_SERVE_B, _PAGE_LEN, _BUCKET = 8, 16, (256, 64)


def _serve_shapes(rep, dtype=jnp.float32):
    """(params, pages, group, st) abstract shapes of the default engine's
    widest bucket for the chip_smoke LM — taken from the engine's own
    geometry helpers, not re-derived."""
    from marlin_tpu.config import get_config
    from marlin_tpu.models.transformer import TransformerLM, init_kv_pages
    from marlin_tpu.serving.kvpool import PagedGroup, auto_num_pages

    cfg = get_config()
    assert (cfg.serve_max_batch, cfg.serve_page_len) == (_SERVE_B, _PAGE_LEN)
    assert _BUCKET in cfg.serve_buckets
    lm = TransformerLM(vocab=4096, d_model=512, heads=8, layers=4, seed=0)

    def sds(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                           sharding=rep), tree)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    params = sds(jax.eval_shape(lm.init_params))
    npages = auto_num_pages(cfg.serve_buckets, _SERVE_B, _PAGE_LEN)
    pages = sds(jax.eval_shape(
        lambda pp: init_kv_pages(pp, npages, _PAGE_LEN, 8,
                                 jnp.dtype(dtype).name), params))
    group = PagedGroup(_BUCKET, _SERVE_B, _PAGE_LEN, cfg.serve_prefill_chunk)
    return params, pages, group, st


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_decode_kernel_mosaic_compiles(one_chip, dtype):
    """The fused paged decode-attention kernel through real Mosaic at the
    serving width (B 8, kv_heads 8, group 1, dh 64, page_len 16)."""
    from marlin_tpu.ops.paged_attention import _paged_decode_attention_call

    _, pages, group, st = _serve_shapes(one_chip, dtype)
    slab = pages["l0"][0]
    q = st((_SERVE_B, 8, 1, 64), dtype)
    c = _paged_decode_attention_call.trace(
        q, slab, slab, st((_SERVE_B, group.pages_per_row)), st((_SERVE_B,)),
        page_len=_PAGE_LEN, interpret=False).lower().compile()
    assert "tpu_custom_call" in c.as_text()


def test_lm_decode_paged_pallas_compiles_for_v5e(one_chip):
    """The default engine's decode program on a TPU — ``lm_decode_paged``
    with the Pallas kernel inside, fed from the device as the engine feeds
    it (``prev_tokens`` / ``prev_index``) — whole, for one v5e chip."""
    from marlin_tpu.models.transformer import _lm_decode_paged_jit

    params, pages, group, st = _serve_shapes(one_chip)
    B = _SERVE_B
    with mt.config_context(pallas_interpret=False):
        c = _lm_decode_paged_jit.trace(
            params, pages, st((B, group.pages_per_row)), st((B,)), st((B,)),
            st((B,)), st((B,), jnp.uint32), st((B,), jnp.float32),
            st((B,), jnp.float32), st((B,)), heads=8, page_len=_PAGE_LEN,
            compute_dtype=None, moe=None, kernel="pallas",
            prev_tokens=st((B,)), prev_index=st((B,))).lower().compile()
    text = c.as_text()
    assert "tpu_custom_call" in text
    assert c.memory_analysis().peak_memory_in_bytes < 1024**3
    # the sampling tail survives the TPU compiler as ONE real conditional
    # (not flattened into a select), with the vocabulary sorts in a branch
    # computation and none in the entry computation greedy batches run
    assert text.count(" conditional(") == 1
    entry = text[text.index("\nENTRY "):]
    assert " conditional(" in entry and " sort(" not in entry
    assert " sort(" in text


def test_lm_prefill_paged_compiles_for_v5e(one_chip):
    """The chunked paged prefill program at the same geometry."""
    from marlin_tpu.models.transformer import _lm_prefill_paged_jit

    params, pages, group, st = _serve_shapes(one_chip)
    c = _lm_prefill_paged_jit.trace(
        params, pages, st((group.table_width,)), st((group.chunk,)), st(()),
        st(()), st((), jnp.uint32), st((), jnp.float32), st((), jnp.float32),
        st(()), heads=8, page_len=_PAGE_LEN, compute_dtype=None,
        moe=None).lower().compile()
    assert c.memory_analysis().peak_memory_in_bytes < 1024**3


def test_rmm_20000_compiles_for_2x2_v5e(topo_mesh):
    """The 20000² ``multiply(strategy="rmm", split=(2, 2, 1))`` program (the
    fused ``matmul_padded`` path ``DenseVecMatrix.multiply`` dispatches) on
    a 2x2 mesh of described chips: the ``psum`` over the split contraction
    axis must reach the TPU program as an all-reduce, and each chip's share
    must fit its HBM."""
    from marlin_tpu.parallel.matmul import matmul_padded

    mesh = topo_mesh(("rows", "cols"), (2, 2))
    n = 20000
    row = NamedSharding(mesh, P("rows", None))
    blk = NamedSharding(mesh, P("rows", "cols"))
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=row)
    c = jax.jit(lambda x, y: matmul_padded(
        x, y, (n, n, n), blk, (n, n), strategy="rmm", split=(2, 2, 1),
        precision="high")).trace(a, a).lower().compile()
    assert "all-reduce" in c.as_text()
    assert c.memory_analysis().peak_memory_in_bytes < 15 * 1024**3


def _compile_square_cell(topo_mesh, n, shape, **kw):
    """A matmul cell's own program: ``matmul_padded`` of two row-sharded
    ``(n, n)`` float32 operands at ``precision="high"`` on a ``shape`` mesh
    of described chips, as ``DenseVecMatrix.multiply`` dispatches it."""
    from marlin_tpu.parallel.matmul import matmul_padded

    mesh = topo_mesh(("rows", "cols"), shape)
    row = NamedSharding(mesh, P("rows", None))
    out = NamedSharding(mesh, P("rows", "cols") if shape[1] > 1
                        else P("rows", None))
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=row)
    return jax.jit(lambda x, y: matmul_padded(
        x, y, (n, n, n), out, (n, n), precision="high", **kw)) \
        .trace(a, a).lower().compile()


def test_square_mesh4_cell_rides_the_ring_on_2x2_v5e(topo_mesh):
    """``matmul.square-mesh4``'s program (n = 36864, ``auto``): the CARMA
    (2, 1, 2) split as a ring along ``rows``. The one collective is the
    permute of the block of B's column panel a chip lacks, started BEFORE the
    first dot and awaited after it; nothing is reduced or gathered; a chip's
    peak is under the k-split program's 12.23 GB."""
    c = _compile_square_cell(topo_mesh, 36864, (2, 2), strategy="auto")
    text = c.as_text()
    assert "collective-permute" in text
    for gone in ("all-reduce", "reduce-scatter", "all-gather", "all-to-all"):
        assert gone not in text, gone
    entry = text[text.index("ENTRY"):]
    start = entry.index(" collective-permute-start(")
    done = entry.index(" collective-permute-done(")
    assert "f32[18432,18432]" in entry[entry.rindex("\n", 0, start):start]
    dots = [i for i in range(len(entry)) if entry.startswith("kind=kOutput", i)]
    assert len(dots) == 2 and start < dots[0] < done < dots[1]
    peak = c.memory_analysis().peak_memory_in_bytes
    assert peak < 10.5e9 < 12.23e9


def test_explicit_k_split_keeps_its_psum_at_the_cells_size(topo_mesh):
    """``split=(2, 2, 1)`` at the cell's size is still the parent's program:
    a reduction over the split contraction, 12.2 GB a chip."""
    c = _compile_square_cell(topo_mesh, 36864, (2, 2), strategy="rmm",
                             split=(2, 2, 1))
    text = c.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    assert 11.5e9 < c.memory_analysis().peak_memory_in_bytes < 13e9


def test_square_1chip_cell_holds_no_collective(topo_mesh):
    """``matmul.square-1chip``'s program (n = 28672 on a (1, 1) mesh): the
    split is (1, 1, 1) and no collective of any kind exists."""
    text = _compile_square_cell(topo_mesh, 28672, (1, 1),
                                strategy="auto").as_text()
    for gone in ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                 "collective-permute"):
        assert gone not in text, gone
    assert text.count("kind=kOutput") == 1


def _compile_spec_decode_kernel(one_chip, group, page_len, pages, width,
                                rows=32, kvh=8, window=False, flat=True,
                                dtype=jnp.bfloat16, dh=128):
    """A spec model's decode kernel through real Mosaic: ``rows`` rows,
    ``kvh`` KV heads x ``dh``, a slab of ``pages`` pages held as
    :func:`~marlin_tpu.models.hybrid.init_kv_pages` holds them (``flat``:
    a token's heads in one row) and a table ``width`` wide; the plain call,
    or the window call over a ring of ``width``. Returns the compiled
    call."""
    from marlin_tpu.ops.paged_attention import (
        _paged_decode_attention_call, _paged_decode_attention_window_call)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = st((rows, kvh, group, dh), dtype)
    slab = st((pages, page_len, kvh * dh) if flat
              else (pages, page_len, kvh, dh), dtype)
    tables, row = st((rows, width)), st((rows,))
    if window:
        c = _paged_decode_attention_window_call.trace(
            q, slab, slab, tables, row, row, row, page_len=page_len,
            interpret=False).lower().compile()
    else:
        c = _paged_decode_attention_call.trace(
            q, slab, slab, tables, row, page_len=page_len,
            interpret=False).lower().compile()
    assert "tpu_custom_call" in c.as_text()
    # the slab is read in place: no padded or re-laid-out copy before the call
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20
    return c


@pytest.mark.parametrize("group", [6, 9], ids=["full-g6", "sliding-g9"])
def test_paged_window_kernel_mosaic_compiles_at_laguna_widths(one_chip,
                                                              group):
    """The decode kernel at Laguna-S-2.1's widths through real Mosaic: 8 KV
    heads x 128, page_len 128, 32 rows; query groups 6 (full layers, the
    plain call over 64 pages) and 9 (sliding layers, the window call over a
    ring of 5 pages)."""
    pages, width = (1537, 64) if group == 6 else (201, 5)
    _compile_spec_decode_kernel(one_chip, group, 128, pages, width,
                                window=group == 9)


# the four (K, V) decode calls the spec cells make, page_len 256: rows,
# KV heads, group, slab pages, table width, window
_CELL_CALLS = {
    "laguna-full-g6": (32, 8, 6, 769, 32, False),
    "laguna-sliding-g9": (32, 8, 9, 145, 4, True),
    "falconh1-g5": (64, 4, 5, 641, 20, False),
    "olmohybrid-g1": (24, 30, 1, 320, 15, False),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("call", list(_CELL_CALLS))
def test_paged_kernels_mosaic_compile_at_the_laguna_cells_shapes(one_chip,
                                                                 call, dtype):
    """Both variants as ``serve.laguna-longtail32`` runs them (page_len
    256): the plain call over the widest bucket's 32 pages of the 769-page
    slab, the window call over a ring of 4 pages of the 145-page slab;
    ``serve.falconh1-chat64``'s call, 64 rows of 4 KV heads over 20 pages;
    and ``serve.olmohybrid-sessions24``'s, 24 rows of 30 KV heads, one query
    row a head, over 15 pages. Since PR 43 each is the walk: the slabs stay
    in HBM (no temporary: :func:`_compile_spec_decode_kernel`), the kernel
    copies a row's live pages into its ring of slots, and since PR 46 every
    head meets a slot's whole ``(256, kv_heads * 128)`` page in one matmul
    at any group, from a block-diagonal query of R = 48 / 80 / 32 / 32 rows
    built in VMEM; in bfloat16 and at the float32 checks' pages, inside the
    VMEM the kernel asks for (the ring and 4 MiB, 16 MiB at least: under a
    fifth of the chip's 128), of which the 4 MiB hold the accumulator (R,
    kv_heads * 128) in float32, the query beside it and a page's scores and
    probabilities (R, 256) (Laguna's sliding layers in float32, the most:
    0.32 + 0.32 + 0.16 MB)."""
    from marlin_tpu.ops.paged_attention import _kv_slots

    rows, kvh, group, pages, width, window = _CELL_CALLS[call]
    _compile_spec_decode_kernel(one_chip, group, 256, pages, width, rows=rows,
                                kvh=kvh, window=window, dtype=dtype)
    slab = jax.ShapeDtypeStruct((pages, 256, kvh * 128), dtype)
    item = jnp.dtype(dtype).itemsize
    ring = 2 * _kv_slots(slab) * 256 * kvh * 128 * item
    assert ring + (4 << 20) <= 24 << 20
    R = -(-kvh * group // 16) * 16
    assert R * (kvh * 128 * (4 + item) + 2 * 256 * 4) <= 1 << 20


def test_float32_pages_of_256_fit_scoped_vmem_only_in_the_flat_layout(
        one_chip):
    """The float32 checks' blocks (``benchmarks/f32_check_falconh1.py``) at
    the cell's page of 256: held ``(page_len, kvh * dh)`` the kernel
    compiles within the VMEM it asks for (the page is never sliced or laid
    out anew: it meets the block-diagonal query whole); held ``(page_len,
    kvh, dh)`` the other kernel's relayout of a 256-token block asks for
    more than there is (which is why that check ran at pages of 128 until
    the layout changed)."""
    rows, kvh, group, pages, width, _ = _CELL_CALLS["falconh1-g5"]
    _compile_spec_decode_kernel(one_chip, group, 256, pages, width, rows=rows,
                                kvh=kvh, dtype=jnp.float32)
    with pytest.raises(Exception, match="vmem"):
        _compile_spec_decode_kernel(one_chip, group, 256, pages, width,
                                    rows=rows, kvh=kvh, flat=False,
                                    dtype=jnp.float32)


def test_spec_decode_program_holds_no_copy_of_a_slab(one_chip):
    """The decode program of a spec model with Laguna's attention widths (8
    KV heads x 128, 48 / 72 query heads, window 512, pages of 256; one full
    and one sliding layer, everything else small) compiled whole for a v5e:
    both kernels inside, both slabs (403 MB and 76 MB a layer) aliased to
    the outputs, and no temporary the size of one: the entries are written
    and the pages read where the slab lies (a slab the kernel could not read
    as it is would be copied before every call, as a 320-column latent slab
    was: PERF.md, PR 35)."""
    from marlin_tpu.models import hybrid

    cfg = {
        "hidden_size": 256, "head_dim": 128, "num_key_value_heads": 8,
        "num_hidden_layers": 2, "num_attention_heads": 48,
        "layer_types": ["full_attention", "sliding_attention"],
        "num_attention_heads_per_layer": [48, 72],
        "mlp_layer_types": ["dense", "dense"], "sliding_window": 512,
        "intermediate_size": 512, "moe_intermediate_size": 128,
        "shared_expert_intermediate_size": 128, "num_experts": 8,
        "num_experts_per_tok": 2, "moe_routed_scaling_factor": 2.5,
        "vocab_size": 512, "rms_norm_eps": 1e-6,
        "rope_parameters": {
            "full_attention": {"rope_type": "default", "rope_theta": 10000,
                               "partial_rotary_factor": 1},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                                  "partial_rotary_factor": 1}},
        "param_dtype": "bfloat16", "compute_dtype": "bfloat16"}
    spec = hybrid.ModelSpec.from_config(cfg)
    B, page_len, width, ring = 32, 256, 32, 4

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=one_chip), tree)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: hybrid.init_params(spec, jax.random.key(0))))
    pages = sds(jax.eval_shape(
        lambda: hybrid.init_kv_pages(spec, 769, 145, page_len)))
    assert pages["l0"][0].shape == (769, page_len, 8 * 128)
    slabs = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pages))
    with mt.config_context(pallas_interpret=False):
        c = hybrid._lm_decode_paged_spec_jit.trace(
            params, pages, st((B, width)), st((B, ring)), st((B,)), st((B,)),
            st((B,)), st((B,), jnp.uint32), st((B,), jnp.float32),
            st((B,), jnp.float32), st((B,)), spec=spec, page_len=page_len,
            kernel="pallas").lower().compile()
    assert c.as_text().count("tpu_custom_call") >= 2
    m = c.memory_analysis()
    assert m.alias_size_in_bytes >= slabs
    assert m.temp_size_in_bytes < 145 * page_len * 8 * 128 * 2 // 4


def test_dropless_expert_layer_compiles_for_v5e_as_a_grouped_matmul(one_chip):
    """``moe_experts_ffn`` at the published widths (hidden 3072, 64 held
    experts of width 1024, router 256, top-10) for a decode bucket's 32
    rows: on the chip its experts run through the Pallas grouped matmul
    (``megablox.gmm``, a ``tpu_custom_call``), not a dense dot over every
    expert and not XLA's own ``ragged-dot`` lowering."""
    from marlin_tpu.models.moe import moe_experts_ffn

    def st(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    d, e, f = 3072, 64, 1024
    mp = {"router": st((d, 256), jnp.float32), "e_gate": st((e, d, f)),
          "e_up": st((e, d, f)), "e_down": st((e, f, d)),
          "s_gate": st((d, f)), "s_up": st((d, f)), "s_down": st((f, d))}
    with mt.config_context(pallas_interpret=False):
        c = jax.jit(lambda mp, h, valid: moe_experts_ffn(
            mp, h, valid, top_k=10, first_expert=64, routed_scale=2.5)).trace(
                mp, st((32, d)), st((32,), jnp.bool_)).lower().compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") >= 3 and "ragged-dot" not in text


def test_latent_decode_kernel_mosaic_compiles_in_place_at_published_widths(
        one_chip):
    """The latent decode kernel at Mistral-Small-4's widths through real
    Mosaic: 32 heads against an entry of 320 values stored in 384 columns,
    the value its first 256, pages of 256, 32 rows over 70 pages, the row's
    pages copied out of the slab by the kernel itself. The slab is read IN
    PLACE: the compiled call holds no temporary. A slab of 320 columns is
    refused (the chip holds it 384 wide and a page's copy takes whole lane
    tiles), which is why ``LatentSpec.entry_width`` pads."""
    from marlin_tpu.ops.paged_attention import \
        _paged_decode_attention_latent_call

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def compiled(entry):
        return _paged_decode_attention_latent_call.trace(
            st((32, 32, entry), jnp.bfloat16),
            st((2561, 256, entry), jnp.bfloat16), st((32, 70)), st((32,)),
            value_dim=256, interpret=False).lower().compile()

    c = compiled(384)
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20
    with pytest.raises(Exception, match="aligned to tiling"):
        compiled(320)


def test_sigmoid_expert_layer_compiles_for_v5e_at_published_widths(one_chip):
    """``moe_experts_ffn`` under sigmoid scoring at Mistral-Small-4's widths
    (hidden 4096, 32 held experts of width 2048, router 128, top-4) for a
    decode call's 32 rows: the experts run through the grouped matmul
    kernel."""
    from marlin_tpu.models.moe import moe_experts_ffn

    def st(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    d, e, f = 4096, 32, 2048
    mp = {"router": st((d, 128), jnp.float32),
          "e_bias": st((128,), jnp.float32), "e_gate": st((e, d, f)),
          "e_up": st((e, d, f)), "e_down": st((e, f, d)),
          "s_gate": st((d, f)), "s_up": st((d, f)), "s_down": st((f, d))}
    with mt.config_context(pallas_interpret=False):
        c = jax.jit(lambda mp, h, valid: moe_experts_ffn(
            mp, h, valid, top_k=4, first_expert=0,
            scoring="sigmoid")).trace(
                mp, st((32, d)), st((32,), jnp.bool_)).lower().compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") >= 3 and "ragged-dot" not in text


def test_state_update_kernel_mosaic_compiles_in_place_at_published_widths(
        one_chip):
    """The decode state update (``ops/ssm.py``) at the ``falcon_h1`` cell's
    shapes (64 rows, 32 heads of 128 channels, a state of 256 columns in 2
    groups, 65 slots) compiles through Mosaic for a v5e, and updates the
    slab IN PLACE: the 273 MB slab is aliased to the output and no
    temporary of its size appears (a copy of it would cost a layer 0.67 ms
    a step, as much as the update itself)."""
    from marlin_tpu.ops import ssm

    B, H, N, P, G, S = 64, 32, 256, 128, 2, 65
    slab = jax.ShapeDtypeStruct((S, H, N, P), jnp.float32, sharding=one_chip)
    slab_bytes = S * H * N * P * 4

    def st(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(slab, slots, x, dt, A, Bm, Cm, D):
        return ssm.ssd_decode_update(slab, slots, x, dt, A, Bm, Cm, D,
                                     kernel="pallas", interpret=False)

    assert ssm.decode_update_supported(H, G, N, P)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        slab, st((B,), jnp.int32), st((B, H, P)), st((B, H)), st((H,)),
        st((B, G, N)), st((B, G, N)), st((H,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= slab_bytes
    assert m.temp_size_in_bytes < slab_bytes // 8


def test_delta_update_kernel_mosaic_compiles_in_place_at_published_widths(
        one_chip):
    """The decode state update of a delta-rule layer (``ops/delta_rule.py``)
    at the ``olmo_hybrid`` cell's shapes (24 rows, 30 heads, keys of 96,
    values of 192: neither whole lane tiles, the slab ``(53, 96, 5760)``
    is) compiles through Mosaic for a v5e, and updates the slab IN PLACE:
    the 117 MB slab is aliased to the output and no temporary of its size
    appears; so does the snapshot's copy of one slot onto another."""
    from marlin_tpu.ops import delta_rule

    B, H, K, V, S = 24, 30, 96, 192, 53
    slab_bytes = S * K * H * V * 4

    def st(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(slab, slots, q, k, v, g, b):
        return delta_rule.delta_decode_update(slab, slots, q, k, v, g, b,
                                              kernel="pallas",
                                              interpret=False)

    assert delta_rule.decode_heads_block(H, K, V) == 10
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        st((S, K, H * V)), st((B,), jnp.int32), st((B, H, K)),
        st((B, H, K)), st((B, H, V)), st((B, H)), st((B, H))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= slab_bytes
    assert m.temp_size_in_bytes < slab_bytes // 8

    def snapshot(slab, src, dst):
        return slab.at[dst].set(slab[src])

    copied = jax.jit(snapshot, donate_argnums=(0,)).lower(
        st((S, K, H * V)), st((), jnp.int32), st((), jnp.int32)).compile()
    m = copied.memory_analysis()
    assert m.alias_size_in_bytes >= slab_bytes
    assert m.temp_size_in_bytes < slab_bytes // 8


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_kernel_mosaic_compiles_at_eight_heads_of_64(one_chip, dtype):
    """``serve.lfm2-agent96``'s call: 96 rows of 8 KV heads x 64 (two heads
    to a lane tile), four query rows a head, over 24 pages of the 1537-page
    slab, page_len 256. The walk never slices a head's half tile out of the
    page: every head meets the whole ``(256, 512)`` page in one matmul from
    the block-diagonal query, which the kernel builds in VMEM out of the
    row's 32 queries of 64 (repeated across the eight heads' lanes, two
    heads to a lane tile); the one temporary is those rows, 0.4 MB in
    bfloat16 (the caller built the whole (32, 512) a row until PR 46, 3
    MB); the slabs stay in HBM."""
    from marlin_tpu.ops.paged_attention import _paged_decode_attention_call

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows, kvh, group, dh, pages, width = 96, 8, 4, 64, 1537, 24
    slab = st((pages, 256, kvh * dh), dtype)
    c = _paged_decode_attention_call.trace(
        st((rows, kvh, group, dh), dtype), slab, slab, st((rows, width)),
        st((rows,)), page_len=256, interpret=False).lower().compile()
    assert "tpu_custom_call" in c.as_text()
    query = rows * kvh * group * dh * jnp.dtype(dtype).itemsize
    assert c.memory_analysis().temp_size_in_bytes <= 2 * query


def test_lfm2_programs_compile_for_v5e_at_the_cells_sizes(one_chip):
    """Both paged programs of the ``lfm2_moe`` family at the cell's widths,
    rows, page and table (hidden 2048, 32 / 8 heads x 64, dense 7168, ALL 32
    experts of 1792, 65536 rows tied, 96 rows, pages of 256, a table of 24,
    a chunk of 512, 1537 pages, 353 slots), four layers (both dense ones, a
    full-attention layer and a conv layer on experts), compiled whole for a
    v5e: the attention kernel and the six grouped matmuls inside, the slabs
    aliased, the tails ONE array a conv layer, no second table for the head
    and no temporary the size of a slab; so does the snapshot's copy."""
    import json
    import os

    from marlin_tpu.models import hybrid

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "lfm2-8b-a1b-l16.json")) as f:
        cfg = json.load(f)
    eng = cfg["engine"]
    spec = hybrid.ModelSpec.from_config(dict(cfg, num_hidden_layers=4))
    assert [ly.attn for ly in spec.layers] == ["conv", "conv", "full", "conv"]
    B, page_len = eng["max_batch"], eng["page_len"]
    W = sum(eng["buckets"][-1]) // page_len
    slots = eng["state_slots"] + eng["snapshot_slots"]

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=one_chip), tree)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: hybrid.init_params(spec, jax.random.key(0))))
    assert "head" not in params
    pages = sds(jax.eval_shape(lambda: hybrid.init_kv_pages(
        spec, eng["num_pages"], 0, page_len, state_slots=slots)))
    assert [a.shape for a in pages["l3"]] == [(slots, 2, 2048)]
    slab = eng["num_pages"] * page_len * 512 * 2
    with mt.config_context(pallas_interpret=False):
        decode = hybrid._lm_decode_paged_spec_jit.trace(
            params, pages, st((B, W)), st((B, 0)), st((B,)), st((B,)),
            st((B,)), st((B,), jnp.uint32), st((B,), jnp.float32),
            st((B,), jnp.float32), st((B,)), spec=spec, page_len=page_len,
            kernel="pallas", prev_tokens=st((B,)), prev_index=st((B,)),
            state_slots=st((B,))).lower().compile()
        prefill = hybrid._lm_prefill_paged_spec_jit.trace(
            params, pages, st((W + 2,)), st((0,)),
            st((eng["prefill_chunk"],)), st(()), st(()), st((), jnp.uint32),
            st((), jnp.float32), st((), jnp.float32), st(()), spec=spec,
            page_len=page_len, state_slot=st(())).lower().compile()
        copy = hybrid._state_slot_copy_jit.trace(
            pages, st(()), st(()), spec=spec).lower().compile()
    assert decode.as_text().count("tpu_custom_call") >= 1 + 6
    # (the six grouped matmuls and, since PR 55, the chunk scan's kernel)
    assert prefill.as_text().count("tpu_custom_call") >= 6 + 1
    for c in (decode, prefill, copy):
        m = c.memory_analysis()
        assert m.alias_size_in_bytes >= 2 * slab
        assert m.temp_size_in_bytes < slab // 4


def test_the_context_fetch_compiles_for_v5e_and_passes_over_no_slab(one_chip):
    """A prefill chunk's fetch of a row's context at the Olmo-Hybrid cell's
    shapes (``paged_attention.fetch_pages``: a slab of 320 pages x 256
    tokens x 3840 lanes, a table of 17) through real Mosaic: one kernel, no
    operation whose output is as large as the slab or a lane piece of it, no
    temporary. The gather it replaced does hold such operations at this
    width (four ``slice``s of the slab), which is what a chunk paid 15.5 of
    its 51 ms for (PERF.md section 6, PR 45)."""
    import re

    import marlin_tpu as mt
    from marlin_tpu.ops.paged_attention import fetch_pages

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def over_slab(fetch, lanes):
        with mt.config_context(pallas_interpret=False):
            c = jax.jit(fetch).trace(
                st((320, 256, lanes), jnp.bfloat16), st((17,))) \
                .lower().compile()
        ops = re.findall(r"= \w+\[320,256,\d+\]\S* ([\w\-]+)\(", c.as_text())
        return c, [op for op in ops if op != "parameter"]

    c, ops = over_slab(fetch_pages, 3840)
    assert "tpu_custom_call" in c.as_text() and not ops
    assert c.memory_analysis().temp_size_in_bytes < 1 << 20
    _, ops = over_slab(lambda t, tb: t[tb], 3840)
    assert ops.count("slice") == 4


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_block_walk_kernel_mosaic_compiles_at_the_sala_cells_shapes(one_chip,
                                                                    dtype):
    """``serve.minicpm-sala-longdoc16``'s sparse decode call: 16 rows of 2 KV
    heads x 128 with 16 query rows a head, each (row, KV head) a list of up
    to 128 blocks of 64 tokens (a quarter of a 256-token page, ONE head's 128
    lanes: a strided window of the (256, 256) page, its lane offset the grid's
    head), over a table of 136 pages of the 3264-page slab. The slabs stay in
    HBM; the scoped VMEM holds the two block buffers (2 MB each in bfloat16,
    4 MB in float32: the f32 check's); so does the fetch of the rows'
    compressed keys, a page of 16 entries a copy."""
    from marlin_tpu.ops.paged_attention import (
        _fetch_pages_call, _paged_decode_attention_blocks_call)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows, kvh, group, dh, pages, width, slots = 16, 2, 16, 128, 3264, 136, 128
    slab = st((pages, 256, kvh * dh), dtype)
    c = _paged_decode_attention_blocks_call.trace(
        st((rows, kvh, group, dh), dtype), slab, slab, st((rows, width)),
        st((rows, kvh, slots)), st((rows, kvh)), st((rows,)), block=64,
        interpret=False).lower().compile()
    assert "tpu_custom_call" in c.as_text()
    query = rows * kvh * group * dh * jnp.dtype(dtype).itemsize
    assert c.memory_analysis().temp_size_in_bytes <= 4 * query
    f = _fetch_pages_call.trace(st((pages, 16, kvh * dh), dtype),
                                st((rows * width,)),
                                interpret=False).lower().compile()
    assert "tpu_custom_call" in f.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_tile_walk_kernel_mosaic_compiles_at_the_sala_cells_shapes(one_chip,
                                                                   dtype):
    """``serve.minicpm-sala-longdoc16``'s sparse prefill call: a chunk of 512
    queries in 32 tiles of 16 tokens (256 query rows a KV head), each (tile,
    KV head) a list of up to 544 blocks of 64 tokens in rounds of 16 (640
    entries: five rows of words), over a context of 34 816 keys left in HBM;
    the scoped VMEM holds four rounds of K and V (2 MB in bfloat16), the
    spread constant (2 MB) and a round's (256, 1024) score tiles. The lists
    are scalar-prefetched: 64 x 640 words of SMEM."""
    from marlin_tpu.ops.paged_attention import _sparse_prefill_attention_call

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    T, kvh, group, dh, keys, tiles, S = 512, 2, 16, 128, 34816, 32, 640
    ctx = st((keys, kvh * dh), dtype)
    # float32 as the f32 checks run it, under "highest": the kernel's own
    # bfloat16 matmul (the tokens' bits) must not inherit that
    with jax.default_matmul_precision(
            "highest" if dtype == jnp.float32 else "default"):
        c = _sparse_prefill_attention_call.trace(
            st((T, kvh, group, dh), dtype), ctx, ctx, st((T,)),
            st((kvh, tiles, S)), st((kvh, tiles)), st((kvh, tiles, S)),
            block=64, interpret=False).lower().compile()
    assert "tpu_custom_call" in c.as_text()
    query = T * kvh * group * dh * jnp.dtype(dtype).itemsize
    assert c.memory_analysis().temp_size_in_bytes <= 4 * query


def test_lightning_update_kernel_mosaic_compiles_in_place_at_published_widths(
        one_chip):
    """The decode state update of a lightning layer (``ops/lightning.py``) at
    the ``minicpm_sala`` cell's shapes (16 rows, 32 heads of 128 x 128
    float32: sixteen whole tiles a head, 33 slots) compiles through Mosaic
    for a v5e and updates the slab IN PLACE: the 69 MB slab is aliased to the
    output and no temporary of its size appears."""
    from marlin_tpu.ops import lightning

    B, H, K, S = 16, 32, 128, 33
    slab_bytes = S * H * K * K * 4

    def st(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(slab, slots, q, k, v, log_decay):
        return lightning.lightning_decode_update(slab, slots, q, k, v,
                                                 log_decay, kernel="pallas",
                                                 interpret=False)

    assert lightning.decode_update_supported(H, K, K)
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        st((S, H, K, K)), st((B,), jnp.int32), st((B, H, K)), st((B, H, K)),
        st((B, H, K)), st((H,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= slab_bytes
    assert m.temp_size_in_bytes < slab_bytes // 8


def test_kda_update_kernel_mosaic_compiles_in_place_at_published_widths(
        one_chip):
    """The decode state update with a decay a CHANNEL (``ops/delta_rule.py``,
    the ``solar_open2`` cell's shapes: 128 rows, 64 heads of 128 x 128, the
    slab ``(193, 128, 8192)`` whole lane tiles head by head, 16 heads a
    block) compiles through Mosaic for a v5e with the decay entering as a
    column along its head's lanes, and updates the slab IN PLACE: the 810 MB
    slab is aliased to the output and no temporary of its size appears. It
    is the kernel of the ``olmo_hybrid`` cell (the test above): the decay's
    rank picks the operand's form."""
    from marlin_tpu.ops import delta_rule

    B, H, K, V, S = 128, 64, 128, 128, 193
    slab_bytes = S * K * H * V * 4

    def st(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(slab, slots, q, k, v, g, b):
        return delta_rule.delta_decode_update(slab, slots, q, k, v, g, b,
                                              kernel="pallas",
                                              interpret=False)

    assert delta_rule.decode_heads_block(H, K, V) == 16
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        st((S, K, H * V)), st((B,), jnp.int32), st((B, H, K)),
        st((B, H, K)), st((B, H, V)), st((B, H, K)), st((B, H))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= slab_bytes
    assert m.temp_size_in_bytes < slab_bytes // 8


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kda_chunk_scan_kernel_mosaic_compiles_at_published_widths(one_chip,
                                                                   dtype):
    """The chunk scan with a decay a CHANNEL at the ``solar_open2`` cell's
    prefill chunk (512 positions, 64 heads of 128 x 128, blocks of 64, the
    count of tokens a traced scalar) compiles through Mosaic for a v5e as
    ONE custom call, and nothing the size of XLA's form's masked
    exponentials (268 MB a layer) is left in the program: what the call
    keeps beside its operands is under a chunk's float32 output."""
    from marlin_tpu.ops import delta_rule

    T, H, K = 512, 64, 128

    def st(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def scan(q, k, v, g, b, s, valid):
        return delta_rule.delta_chunk_scan(q, k, v, g, b, s, block=64,
                                           valid=valid, interpret=False)

    assert delta_rule.chunk_scan_supported(H, K, K, 64)
    # (under the float32 checks' context too: the kernel pins every
    # product's precision, a bfloat16 one at "highest" does not compile)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(scan).lower(
            st((T, H, K), dtype), st((T, H, K), dtype), st((T, H, K), dtype),
            st((T, H, K)), st((T, H)), st((K, H, K)),
            st((), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1
    assert compiled.memory_analysis().temp_size_in_bytes <= T * H * K * 4


def _ops_as_large_as(text: str, shape: tuple) -> set:
    """The names of a compiled program's operations whose output has
    ``shape``, those that only hand an array on left out."""
    import re

    whole = re.escape("[" + ",".join(map(str, shape)) + "]")
    return {m.group(1) for m in re.finditer(
        r"= \w+" + whole + r"\S* ([\w\-]+)\(", text)} - {
            "parameter", "bitcast", "get-tuple-element"}


@pytest.mark.parametrize("rows, slots, channels, dtype", [
    (128, 193, 24576, jnp.bfloat16), (24, 53, 11520, jnp.bfloat16),
    (64, 65, 5120, jnp.bfloat16), (24, 53, 11520, jnp.float32)])
def test_tail_step_kernel_mosaic_compiles_in_place_at_published_widths(
        one_chip, rows, slots, channels, dtype):
    """The convolution's decode step on tails in their slots
    (``ops/ssm.py:conv_step_slots``) at the three tailed cells' sizes
    (Solar-Open2 128 rows of 193 slots x 3 x 24576, Olmo-Hybrid 24 of 53 x 3
    x 11520: 90 lane tiles a tap, not whole sublane tiles; Falcon-H1 64 of
    65 x 3 x 5120) compiles through Mosaic for a v5e and advances the slab
    IN PLACE and IN HBM: XLA keeps the slab row-major, a slot in one piece,
    the slab is aliased to the output and nothing else in the program is as
    large (left to choose, XLA moved the whole slab into VMEM ahead of the
    call and back behind it)."""
    from marlin_tpu.ops import ssm

    def st(shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def step(slab, slots, u, w, b):
        return ssm.conv_step_slots(slab, slots, u, w, b, kernel="pallas",
                                   interpret=False)

    shape = (slots, *ssm.tail_slot_shape(4, channels))
    assert ssm.conv_slots_supported(channels) and shape[2] == 128
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        st(shape), st((rows,), jnp.int32), st((rows, channels)),
        st((4, channels)), st((channels,))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # XLA keeps the slab row-major, a slot's tiles one after another
    assert "entry_computation_layout={(%s[%s]{2,1,0:T(8,128)" % (
        {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype],
        ",".join(map(str, shape))) in text
    assert _ops_as_large_as(text, shape) == set()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= np.prod(shape) * jnp.dtype(dtype).itemsize
    assert m.temp_size_in_bytes == 0


def test_solaropen2_programs_compile_for_v5e_at_the_cells_sizes(one_chip):
    """Both paged programs of the ``solar_open2`` family at the cell's
    widths, rows, page and table (hidden 4096, 64 / 8 heads x 128, 64 KDA
    heads of 128 x 128, 40 of 320 experts of 1280 in every layer, 24576 rows
    untied, 128 rows, pages of 256, a table of 32, a chunk of 512, 3585
    pages, 193 slots), the GQA layer and one KDA layer (both on experts: the
    first family whose layer keeps a state slot AND holds a share of the
    experts), compiled whole for a v5e: the attention kernel, the state
    update and the six grouped matmuls inside, the slabs aliased and no
    temporary the size of a slab; so does the snapshot's copy. With the two
    KDA layers left out here (0.95 GB of weights each) the programs' peaks
    stay under the chip's 16.9e9."""
    import json
    import os

    from marlin_tpu.models import hybrid

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "solar-open2-ep8-l4.json")) as f:
        cfg = json.load(f)
    eng, share = cfg["engine"], cfg["deployment_share"]
    spec = hybrid.ModelSpec.from_config(
        dict(cfg, num_hidden_layers=2), experts_total=share["experts_total"],
        first_expert=share["first_expert"])
    assert [(ly.attn, ly.ffn) for ly in spec.layers] == [("full", "moe"),
                                                         ("kda", "moe")]
    B, page_len = eng["max_batch"], eng["page_len"]
    W = sum(eng["buckets"][-1]) // page_len
    slots = eng["state_slots"] + eng["snapshot_slots"]

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=one_chip), tree)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: hybrid.init_params(spec, jax.random.key(0))))
    pages = sds(jax.eval_shape(lambda: hybrid.init_kv_pages(
        spec, eng["num_pages"], 0, page_len, state_slots=slots)))
    assert [a.shape for a in pages["l1"]] == [(slots, 128, 8192),
                                              (slots, 576, 128)]
    kv = 2 * eng["num_pages"] * page_len * 1024 * 2
    state = slots * spec.state_slot_bytes()
    assert spec.state_slot_bytes() == 4194304 + 147456
    with mt.config_context(pallas_interpret=False):
        decode = hybrid._lm_decode_paged_spec_jit.trace(
            params, pages, st((B, W)), st((B, 0)), st((B,)), st((B,)),
            st((B,)), st((B,), jnp.uint32), st((B,), jnp.float32),
            st((B,), jnp.float32), st((B,)), spec=spec, page_len=page_len,
            kernel="pallas", prev_tokens=st((B,)), prev_index=st((B,)),
            state_slots=st((B,))).lower().compile()
        prefill = hybrid._lm_prefill_paged_spec_jit.trace(
            params, pages, st((W + 2,)), st((0,)),
            st((eng["prefill_chunk"],)), st(()), st(()), st((), jnp.uint32),
            st((), jnp.float32), st((), jnp.float32), st(()), spec=spec,
            page_len=page_len, state_slot=st(())).lower().compile()
        copy = hybrid._state_slot_copy_jit.trace(
            pages, st(()), st(()), spec=spec).lower().compile()
    assert decode.as_text().count("tpu_custom_call") >= 1 + 1 + 1 + 6
    # (the six grouped matmuls and, since PR 55, the chunk scan's kernel)
    assert prefill.as_text().count("tpu_custom_call") >= 6 + 1
    # the tails: a slot in one piece, advanced in place by its own kernel; no
    # operation of the decode program makes another array of their size
    # (until PR 52: two whole copies, a gather and a scatter a layer)
    assert f"bf16[{slots},576,128]{{2,1,0:T(8,128)(2,1)}} parameter" \
        in decode.as_text()
    assert _ops_as_large_as(decode.as_text(), (slots, 576, 128)) == set()
    for c in (decode, prefill, copy):
        m = c.memory_analysis()
        assert m.alias_size_in_bytes >= kv + state
        assert m.temp_size_in_bytes < min(kv, state) // 2
        assert m.peak_memory_in_bytes < 16.9e9


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_index_score_kernels_mosaic_compile_at_the_deepseekv32_cells_shapes(
        one_chip, dtype):
    """``serve.deepseekv32-longctx32``'s index scores: a prefill chunk's 1024
    queries x 64 index heads of 128 against the row's 67,584 index keys (tiles
    of 16 queries x 512 keys: one matmul a tile, the ReLU, the head weights
    and the sum over a query's heads on the tile in VMEM), and a decode
    call's 32 rows walking their own 264 pages of 256 keys out of the
    index-key slab, which stays in HBM (float32: the f32 check's)."""
    from marlin_tpu.ops.dsa import (_dsa_index_chunk_call,
                                    _dsa_index_paged_call)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    T, J, D, L, B, W, page = 1024, 64, 128, 67584, 32, 264, 256
    c = _dsa_index_chunk_call.trace(
        st((T, J, D), dtype), st((T, J), jnp.float32), st((L, D), dtype),
        st((2,)), tq=16, tk=512, interpret=False).lower().compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().temp_size_in_bytes < T * L * 4 // 8
    p = _dsa_index_paged_call.trace(
        st((B, J, D), dtype), st((B, J), jnp.float32),
        st((2464, page, D), dtype), st((B, W)), st((B,)),
        interpret=False).lower().compile()
    assert "tpu_custom_call" in p.as_text()
    assert p.memory_analysis().temp_size_in_bytes < B * W * page * 4


@pytest.mark.parametrize("precision", [None, "highest"],
                         ids=["default", "highest"])
@pytest.mark.parametrize("L", [67584, 68608], ids=["decode", "prefill"])
def test_select_kernel_mosaic_compiles_at_the_deepseekv32_cells_shapes(
        one_chip, L, precision):
    """``serve.deepseekv32-longctx32``'s selection, one tile: 32 rows' index
    scores over the decode program's 264 pages of 256 keys and over the
    prefill program's context (268 pages: its last piece of 2048 positions
    lies past the row in part), ``index_topk`` 2048, as ONE kernel whose
    scores never come back to HBM; under a default matmul precision of
    "highest" too (the f32 check's: the prefix counts' product names its
    own)."""
    import contextlib

    from marlin_tpu.ops import dsa

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert dsa.select_kernel_supported(32, L, 2048)
    # inside the VMEM a call has without asking: beside a call that names a
    # limit XLA keeps less of the program's own arrays in VMEM (PR 57: the
    # decode program's gathered entries left it, 0.82 -> 1.00 ms a layer)
    assert dsa._select_vmem_bytes(L, 2048) <= dsa._SCOPED_VMEM
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        c = dsa._dsa_select_call.trace(
            st((32, L), jnp.float32), st((32,)), k=2048,
            interpret=False).lower().compile()
    text = c.as_text()
    assert text.count("tpu_custom_call") == 1 and " while(" not in text
    assert c.memory_analysis().temp_size_in_bytes < 32 * L * 4 // 8


def _deepseekv32_cell(one_chip, layers):
    """The cell's configuration cut to ``layers`` layers: ``(spec, engine
    block, abstract params, abstract pages)``."""
    import json
    import os

    from benchmarks.drivers import serve_deepseekv32 as driver
    from marlin_tpu.models import hybrid

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "deepseek-v32-ep16-l5.json")) as f:
        cfg = json.load(f)
    spec = driver.model_spec(dict(cfg, num_hidden_layers=layers))

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=one_chip), tree)

    eng = cfg["engine"]
    return (spec, eng,
            sds(jax.eval_shape(
                lambda: hybrid.init_params(spec, jax.random.key(0)))),
            sds(jax.eval_shape(lambda: hybrid.init_kv_pages(
                spec, eng["num_pages"], 0, eng["page_len"]))))


def _paged_programs(one_chip, spec, eng, params, pages):
    """The traced ``(prefill, decode)`` programs of a spec cell at its
    engine's rows, page, table and chunk."""
    from marlin_tpu.models import hybrid

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    B, page_len = eng["max_batch"], eng["page_len"]
    W = sum(eng["buckets"][-1]) // page_len
    with mt.config_context(pallas_interpret=False):
        return (
            hybrid._lm_prefill_paged_spec_jit.trace(
                params, pages, st((W + 1,)), st((0,)),
                st((eng["prefill_chunk"],)), st(()), st(()),
                st((), jnp.uint32), st((), jnp.float32), st((), jnp.float32),
                st(()), spec=spec, page_len=page_len).lower(),
            hybrid._lm_decode_paged_spec_jit.trace(
                params, pages, st((B, W)), st((B, 0)), st((B,)), st((B,)),
                st((B,)), st((B,), jnp.uint32), st((B,), jnp.float32),
                st((B,), jnp.float32), st((B,)), spec=spec,
                page_len=page_len, kernel="pallas").lower())


def test_deepseekv32_programs_hold_one_selection_kernel_a_layer(one_chip):
    """Both paged programs of the ``deepseek_v32`` family at the cell's
    widths, rows, page, table and chunk, two layers (the dense one and one
    on experts), compiled whole for a v5e: under the ``dsa_select`` scope
    each holds ONE custom call a layer, named ``dsa_select``, and no while
    loop (until PR 57: the radix search's sixteen counting passes, a loop of
    XLA operations out of HBM, and a conditional around the ties' ranks)."""
    layers = 2
    compiled = [p.compile() for p in _paged_programs(
        one_chip, *_deepseekv32_cell(one_chip, layers))]
    for c in compiled:
        scoped = [ln for ln in c.as_text().split("\n")
                  if "/dsa_select/" in ln]
        kernels = [ln for ln in scoped if " custom-call(" in ln]
        assert len(kernels) == layers
        assert all(ln.lstrip().startswith("%dsa_select") for ln in kernels)
        assert not [ln for ln in scoped
                    if " while(" in ln or " conditional(" in ln]
        assert c.memory_analysis().peak_memory_in_bytes < 16.9e9


def test_mistral4_programs_do_not_see_the_selection_kernel(one_chip,
                                                           monkeypatch):
    """``serve.mistral4-docqa32`` shares ``attend_latent`` and the latent
    kernels with the DeepSeek-V3.2 cell but has no indexer: its two programs
    lower to the same text whether the selection may take its kernel or not
    (the parent's text: sha1 9135909138... / 9e52a4d662... from the parent's
    path, PERF.md section 6 PR 57), and name no selection."""
    import json
    import os

    from benchmarks.drivers import serve_mistral4 as driver
    from marlin_tpu.models import hybrid
    from marlin_tpu.ops import dsa

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           "mistral-small4-ep4-l6.json")) as f:
        cfg = json.load(f)
    spec, eng = driver.model_spec(cfg), cfg["engine"]
    assert spec.latent.indexer is None

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(
        lambda: hybrid.init_params(spec, jax.random.key(0))))
    pages = sds(jax.eval_shape(lambda: hybrid.init_kv_pages(
        spec, eng["num_pages"], 0, eng["page_len"])))
    texts = []
    for kernel in (True, False):   # (one line lowers both: a kernel's text
        if not kernel:             # holds the lines it was reached from)
            monkeypatch.setattr(dsa, "select_kernel_supported",
                                lambda *a: False)
        jax.clear_caches()
        texts.append([p.as_text() for p in _paged_programs(
            one_chip, spec, eng, params, pages)])
    assert texts[0] == texts[1]
    assert not any("dsa_select" in text for text in texts[0])
