"""Full benchmark sweep over the BASELINE.md measurement configs.

Writes one JSON object per config to stdout (one per line) and a summary table
to BENCHMARKS.md. ``bench.py`` remains the single-line headline driver; this
is the RMMcompare-style wider harness.

Configs (BASELINE.md) — the default sweep runs 1-5; the extras run only when
named (``python bench_all.py lu chol attn``) because they are additions beyond
the BASELINE config list:
  1. 100×100 file-based multiply (genmat data), CPU-comparable
  2. 4000×4000 dense multiply, single chip
  3. 20000×20000 dense multiply (bf16: same multiply, bf16 MXU operands)
  4. tall-skinny ×512 Gramian, host-streamed (out-of-core)
  5. sparse 10⁶×10⁶ @ 1e-4 density × dense 10⁶×256 (ELL SpMM)
  lu / chol: 8192² distributed blocked factorizations
  attn: 32768×128 causal ring attention
  pr: PageRank on a 10⁷-node / 10⁸-edge random graph (edge-list operator)
  acc: north-star multiply row-block rel-err vs host f64 oracle + precision
       kwarg plumbing proof (default bf16 vs high f32)
  als: blocked ALS, 10^6 users x 10^5 items x rank 32 x 10^7 ratings
  bsr: structured-sparsity SpMM (5% of 128x128 blocks), chunked vs pallas
  svd: top-8 SVD of 10^6 x 512 via the dist-eigs Gramian+Lanczos path
  nn: MLP training steps/s, 262k x 784 synthetic MNIST-shaped, batch 8192
  lct: long-context LM training tokens/s, 32k-token causal stream
  lct_long: the longest-sequence training run one chip holds (256k+ tokens,
       remat + chunked LM head; MARLIN_BENCH_LCT_SEQ scales it)
  attn_long: pure causal flash attention at 256k+ tokens
       (MARLIN_BENCH_ATTN_SEQ scales it)
  decode: KV-cached autoregressive decode tokens/s (prefill vs per-token)
  serve: continuous-batching engine offered-load sweep — p50/p99 latency and
       tokens/s per offered rate (MARLIN_BENCH_SERVE_* env knobs scale it)
"""

import collections
import contextlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

RESULTS = []

# Provenance stamp for every measurement taken by THIS run (round-3 verdict
# #9: an unlabeled table invites quoting stale numbers as current). The date
# is always stamped (it can never silently go stale); the round label only
# when MARLIN_BENCH_ROUND is set (the caller pins it) — a hard-coded
# round here would mislabel every future round's numbers.
ROUND = os.environ.get("MARLIN_BENCH_ROUND", "")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


RESULTS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_ALL.json")


def record(name, value, unit, detail="", extra=None):
    # 2 decimals for human-scale values; 3 significant digits below that so
    # rel-err records (~1e-6) don't round to a meaningless 0.0
    rounded = round(value, 2) if abs(value) >= 0.01 else float(f"{value:.3g}")
    stamp = f"{ROUND} {time.strftime('%Y-%m-%d')}".strip()
    entry = {"config": name, "value": rounded, "unit": unit, "detail": detail,
             "measured": stamp}
    if extra:  # ride-along fields (e.g. roofline_frac) — tools/bench_compare
        entry.update(extra)  # shows them next to the gated value
    RESULTS.append(entry)
    print(json.dumps(entry), flush=True)


def _roofline_extra(flops, nbytes, seconds):
    """{"roofline_frac": ...} for one measured program, or None where peaks
    are unknown — BENCH rounds track utilization next to throughput
    (obs/perf.py; CPU peaks are nominal placeholders, TPU peaks are the
    generation table / config overrides)."""
    from marlin_tpu.obs import perf

    pf, bw = perf.peak_rates()
    frac = perf.roofline(flops, nbytes, seconds, pf, bw)["roofline_frac"]
    return {"roofline_frac": round(frac, 4)} if frac is not None else None


def sync(x):
    import jax

    jax.block_until_ready(x)
    return jax.device_get(x.ravel()[0] if hasattr(x, "ravel") else x)


def config1():
    import marlin_tpu as mt

    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    subprocess.run(["make", "-s", "-C", tools], check=True)
    with tempfile.TemporaryDirectory() as d:
        for name, seed in (("a", 1), ("b", 2)):
            with open(os.path.join(d, f"{name}.txt"), "w") as f:
                subprocess.run([os.path.join(tools, "genmat"), "100", "100", str(seed)],
                               stdout=f, check=True)
        mesh = mt.create_mesh()
        a = mt.load_matrix_file(os.path.join(d, "a.txt"), mesh)
        b = mt.load_matrix_file(os.path.join(d, "b.txt"), mesh)
        mt.evaluate(a.multiply(b))
        t0 = time.perf_counter()
        mt.evaluate(a.multiply(b))
        dt = time.perf_counter() - t0
    record("1_file_100x100", dt * 1e3, "ms", "file-loaded multiply incl. sync")


def _dense_config(n, reps, name, precision="high"):
    import jax.numpy as jnp

    import marlin_tpu as mt

    mesh = mt.create_mesh()
    a = mt.DenseVecMatrix.random(0, n, n, mesh=mesh)
    b = mt.DenseVecMatrix.random(1, n, n, mesh=mesh)
    float(jnp.sum(a.data) + jnp.sum(b.data))
    c = a.multiply(b, precision=precision)
    float(jnp.sum(c.data))
    t0 = time.perf_counter()
    for _ in range(reps):
        c = a.multiply(b, precision=precision)
    float(jnp.sum(c.data))
    dt = (time.perf_counter() - t0) / reps
    itemsize = jnp.dtype(a.data.dtype).itemsize
    record(name, 2 * n**3 / dt / 1e9, "GFLOP/s",
           f"{dt * 1e3:.1f} ms/multiply, precision={precision}",
           extra=_roofline_extra(2.0 * n**3, 3.0 * n * n * itemsize, dt))


def config4():
    from marlin_tpu.parallel import streamed_gramian
    from marlin_tpu.utils.profiling import StageTimes

    # BASELINE names 10^7 rows; GFLOP/s is row-count invariant for this
    # streamed kernel — stream 4M rows (8 GB) to keep the bench slot short.
    rows = int(os.environ.get("MARLIN_BENCH_TALL_ROWS", 4_000_000))
    cols = 512
    chunk = int(os.environ.get("MARLIN_BENCH_CHUNK_ROWS", 1 << 19))
    # MARLIN_BENCH_PREFETCH=0 forces the synchronous path (the before/after
    # control for the async prefetch pipeline); default follows config (on)
    prefetch = (False if os.environ.get("MARLIN_BENCH_PREFETCH") == "0"
                else None)
    rng = np.random.default_rng(0)

    def chunks():
        done = 0
        while done < rows:
            size = min(chunk, rows - done)
            yield rng.random((size, cols), np.float32)
            done += size

    # warm-up compile on one chunk
    streamed_gramian(iter([np.zeros((1024, cols), np.float32)]))
    stats = StageTimes()
    t0 = time.perf_counter()
    g = streamed_gramian(chunks(), chunk_rows=chunk, prefetch=prefetch,
                         stats=stats)
    dt = time.perf_counter() - t0
    assert g.shape == (cols, cols)
    # label from the RESOLVED mode: prefetch=None follows config, which may
    # itself be off — the A/B record must say what actually ran
    from marlin_tpu.config import get_config as _get_cfg

    effective = _get_cfg().prefetch_enabled if prefetch is None else prefetch
    mode = "prefetch" if effective else "sync"
    record(f"4_tall_skinny_{rows}x512_gramian_e2e",
           2 * rows * cols**2 / dt / 1e9, "GFLOP/s",
           f"{dt:.1f} s end-to-end incl. host generation + H2D transfer "
           f"[{mode}; stages: {stats.summary()}]",
           extra=_roofline_extra(2.0 * rows * cols**2,
                                 4.0 * rows * cols, dt))

    # device-compute half of the split: the same per-chunk rank-update with
    # the operand already resident, sync-amortized over reps — what the
    # kernel does once data is on chip.
    import jax
    import jax.numpy as jnp
    from marlin_tpu.config import get_config

    @jax.jit
    def rank_update(acc, x):
        return acc + jnp.dot(x.T, x, precision=get_config().matmul_precision)

    x = jnp.asarray(rng.random((chunk, cols), np.float32))
    acc = jnp.zeros((cols, cols), jnp.float32)
    sync(rank_update(acc, x))  # compile + warm
    reps = 10
    t0 = time.perf_counter()
    for _ in range(reps):
        acc = rank_update(acc, x)
    sync(acc)
    dev_dt = (time.perf_counter() - t0) / reps
    record(f"4_tall_skinny_{rows}x512_gramian_device",
           2 * chunk * cols**2 / dev_dt / 1e9, "GFLOP/s",
           f"{dev_dt * 1e3:.1f} ms per {chunk}-row rank-update, data resident")

    _config4_file_legs()


def _config4_file_legs():
    """The data-plane A/B at the config-4 shape, fed from DISK: the same
    streamed Gramian with chunks produced by (a) the Python text parser
    (``MARLIN_BENCH_NATIVE_PLANE=0`` runs only this control leg) and (b) the
    native chunkstore sidecar (``=1`` only this; unset runs both, text
    first). The gap between the legs is what marlin_tpu/io/chunkstore.py
    exists to close. Each record's detail carries the producer-stage
    breakdown (produce = parse / mcs_read+convert, transfer = device_put,
    stall = un-overlapped producer latency the consumer actually waited out,
    compute, drain — utils/profiling.StageTimes). MARLIN_BENCH_FILE_ROWS
    sizes the file (default 65536 x 512 — ~300 MB of text, tractable for
    the Python-parser control; GFLOP/s is row-count invariant here)."""
    from marlin_tpu import native
    from marlin_tpu.io.chunkstore import transcode_text
    from marlin_tpu.io.text import load_matrix_file_out_of_core
    from marlin_tpu.parallel import streamed_gramian
    from marlin_tpu.utils.profiling import StageTimes

    rows = int(os.environ.get("MARLIN_BENCH_FILE_ROWS", 65536))
    cols = 512
    chunk = min(rows, 8192)
    plane = os.environ.get("MARLIN_BENCH_NATIVE_PLANE", "")
    legs = {"0": ("text",), "1": ("native",)}.get(plane, ("text", "native"))
    tools = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools")
    subprocess.run(["make", "-s", "-C", tools], check=True)
    gflop = 2 * rows * cols**2 / 1e9
    speeds = {}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "tall.txt")
        with open(path, "w") as f:
            subprocess.run([os.path.join(tools, "genmat"), str(rows),
                            str(cols), "7"], stdout=f, check=True)
        log(f"config4 file legs: {os.path.getsize(path) / 1e6:.0f} MB text, "
            f"legs={legs}")
        # warm the chunk programs (full + tail shapes) so neither leg pays a
        # compile inside its timed pass
        streamed_gramian(iter([np.zeros((chunk, cols), np.float64),
                               np.zeros((rows % chunk or chunk, cols),
                                        np.float64)]))
        for leg in legs:
            if leg == "native":
                # the A/B is meaningless if the "native" leg silently fell
                # back to the text parser — refuse rather than mislabel
                if not native.chunkstore_available():
                    raise RuntimeError("native chunkstore library "
                                       f"unavailable: {native.build_error()}")
                t0 = time.perf_counter()
                transcode_text(path, chunk_rows=chunk)
                build_s = time.perf_counter() - t0
                ooc = load_matrix_file_out_of_core(path, chunk_rows=chunk)
                assert "chunkstore" in repr(ooc), "sidecar not auto-selected"
                note = f"sidecar built in {build_s:.1f} s (one-time); "
            else:
                ooc = load_matrix_file_out_of_core(path, chunk_rows=chunk,
                                                   chunkstore=False)
                note = "Python text parse every pass; "
            stats = StageTimes()
            t0 = time.perf_counter()
            g = ooc.gramian(stats=stats)
            dt = time.perf_counter() - t0
            assert g.shape == (cols, cols)
            speeds[leg] = gflop / dt
            if leg == "native" and "text" in speeds:
                note += (f"{speeds['native'] / speeds['text']:.1f}x the text "
                         "plane; ")
            record(f"4_file_{rows}x512_gramian_{leg}_plane", gflop / dt,
                   "GFLOP/s", f"{dt:.1f} s end-to-end from disk "
                   f"[{note}stages: {stats.summary()}]")


def config5():
    import marlin_tpu as mt
    from marlin_tpu.ops.sparse_ell import ell_from_coo, ell_spmm

    m = n = 1_000_000
    density, p = 1e-4, 256
    nnz = int(m * n * density)
    rng = np.random.default_rng(0)
    log(f"building ELL with {nnz:.0f} nnz...")
    rows = rng.integers(0, m, nnz, dtype=np.int64)
    cols = rng.integers(0, n, nnz, dtype=np.int64)
    vals = rng.random(nnz, dtype=np.float32)
    t0 = time.perf_counter()
    ell = ell_from_coo(rows, cols, vals, (m, n))
    log(f"ELL built in {time.perf_counter() - t0:.1f}s, K={ell.k_width}")
    b = rng.random((n, p), dtype=np.float32)
    import jax.numpy as jnp

    b_dev = jnp.asarray(b)
    out = ell_spmm(ell, b_dev)
    sync(out)
    t0 = time.perf_counter()
    out = ell_spmm(ell, b_dev)
    sync(out)
    dt = time.perf_counter() - t0
    record("5_spmm_1e6_1e-4_x256", 2 * nnz * p / dt / 1e9, "GFLOP/s",
           f"{dt * 1e3:.0f} ms, ELL K={ell.k_width}")


def config_lu(n=8192):
    import jax.numpy as jnp

    import marlin_tpu as mt

    mesh = mt.create_mesh()
    base = mt.BlockMatrix.random(0, n, n, mesh=mesh)
    a = base.add(mt.BlockMatrix.from_array(float(n) * np.eye(n, dtype=np.float32), mesh))
    float(jnp.sum(a.data))
    reps = 3  # amortize the sync round-trip
    # block pivot = the reference's strategy; the extra masked+panel leg
    # quantifies what LAPACK-style full-height panel pivoting costs on top
    legs = (("masked", "block"), ("shrinking", "block"),
            ("masked", "panel"))  # panel pivoting keeps the masked loop
    for sched, piv in legs:
        l, u, p = a.lu_decompose(mode="dist", schedule=sched, pivot=piv)
        float(jnp.sum(l.data) + jnp.sum(u.data))  # compile + materialize
        t0 = time.perf_counter()
        for _ in range(reps):
            l, u, p = a.lu_decompose(mode="dist", schedule=sched, pivot=piv)
        float(jnp.sum(l.data) + jnp.sum(u.data))
        dt = (time.perf_counter() - t0) / reps
        tag = sched if piv == "block" else f"{sched}_panelpivot"
        record(f"lu_dist_{n}_{tag}", (2 / 3) * n**3 / dt / 1e9, "GFLOP/s",
               f"{dt:.2f} s, pivot={piv}")


def config_cholesky(n=8192):
    import jax.numpy as jnp

    import marlin_tpu as mt

    mesh = mt.create_mesh()
    r = mt.BlockMatrix.random(0, n, n, mesh=mesh)
    a = r.multiply(r.transpose(), precision="high").add(
        mt.BlockMatrix.from_array(float(n) * np.eye(n, dtype=np.float32), mesh)
    )
    float(jnp.sum(a.data))
    reps = 3
    for sched in ("masked", "shrinking"):
        l = a.cholesky_decompose(mode="dist", schedule=sched)
        float(jnp.sum(l.data))
        t0 = time.perf_counter()
        for _ in range(reps):
            l = a.cholesky_decompose(mode="dist", schedule=sched)
        float(jnp.sum(l.data))
        dt = (time.perf_counter() - t0) / reps
        record(f"cholesky_dist_{n}_{sched}", (1 / 3) * n**3 / dt / 1e9,
               "GFLOP/s", f"{dt:.2f} s")


def config_attention(seq=32768, d=128, variants=None, reps=10):
    import jax.numpy as jnp

    import marlin_tpu as mt

    mesh = mt.create_mesh()
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((seq, d)).astype(np.float32))
               for _ in range(3))
    flops = 2.0 * seq * seq * d  # causal: qk^T + pv, halved by the mask
    # reps amortize the sync round-trip out of the figure
    for backend, prec in variants or (("xla", "high"), ("flash", "high"),
                                      ("flash", "default")):
        out = mt.ring_attention(q, k, v, mesh, causal=True, backend=backend,
                                precision=prec)
        float(jnp.sum(out))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = mt.ring_attention(q, k, v, mesh, causal=True,
                                    backend=backend, precision=prec)
        float(jnp.sum(out))
        dt = (time.perf_counter() - t0) / reps
        tag = backend if prec == "high" else f"{backend}_bf16"
        record(f"ring_attention_{seq}x{d}_{tag}", flops / dt / 1e9,
               "GFLOP/s", f"{dt * 1e3:.0f} ms causal")


def config_pagerank(n=10_000_000, e=100_000_000, iterations=10):
    from marlin_tpu.ml import build_transition_operator, pagerank

    rng = np.random.default_rng(0)
    edges = np.empty((e, 2), np.int64)
    edges[:, 0] = rng.integers(0, n, e)
    edges[:, 1] = rng.integers(0, n, e)
    op = build_transition_operator(edges, n=n)
    del edges
    r = pagerank(op, iterations=1)  # compile + H2D transfer
    t0 = time.perf_counter()
    r = pagerank(op, iterations=iterations)
    dt = time.perf_counter() - t0
    assert abs(float(r.sum()) - 1.0) < 1e-3
    record(f"pagerank_{n}n_{e}e", dt / iterations * 1e3, "ms/iter",
           f"{dt:.2f} s for {iterations} iters, edges resident on chip")


def config_bsr(grid=256, bs=128, p=256, block_density=0.05):
    """Structured-sparsity SpMM: (grid·bs)² matrix holding ``block_density``
    of its bs×bs blocks, times a dense (n, p) panel — chunked-einsum vs the
    scatter-free Pallas kernel."""
    import jax.numpy as jnp

    from marlin_tpu.ops.sparse_bsr import BsrMatrix, bsr_spmm, bsr_spmm_pallas

    rng = np.random.default_rng(0)
    n = grid * bs
    nnzb = max(1, int(grid * grid * block_density))
    ids = np.sort(rng.choice(grid * grid, nnzb, replace=False))
    blocks = rng.standard_normal((nnzb, bs, bs)).astype(np.float32)
    bsr = BsrMatrix(jnp.asarray(blocks),
                    jnp.asarray(ids // grid, jnp.int32),
                    jnp.asarray(ids % grid, jnp.int32), (n, n), bs)
    b = jnp.asarray(rng.standard_normal((n, p)).astype(np.float32))
    flops = 2.0 * nnzb * bs * bs * p
    # the Pallas leg runs the Mosaic kernel on TPU; in interpret mode (CPU)
    # it is minutes per call at this scale — a debugging path, not a
    # measurement — so it defaults off unless a real TPU backend is up
    import jax as _jax

    run_pallas = os.environ.get(
        "MARLIN_BENCH_BSR_PALLAS",
        "1" if _jax.default_backend() == "tpu" else "0") != "0"
    legs = [("chunked", lambda: bsr_spmm(bsr, b))]
    if run_pallas:
        legs.append(("pallas", lambda: bsr_spmm_pallas(bsr, b)))
    else:
        log("bsr pallas leg skipped (interpret mode; "
            "MARLIN_BENCH_BSR_PALLAS=1 forces)")
    for name, fn in legs:
        out = fn()
        float(jnp.sum(out))
        t0 = time.perf_counter()
        for _ in range(5):
            out = fn()
        float(jnp.sum(out))
        dt = (time.perf_counter() - t0) / 5
        record(f"bsr_{n}x{n}_bd{block_density}_{name}", flops / dt / 1e9,
               "GFLOP/s", f"{dt * 1e3:.1f} ms, nnzb={nnzb}, bs={bs}, p={p}")

    # the generated-family record: the autotune ranking over chunked-chunk
    # variants + the Pallas kernel picks the dispatch winner (what
    # backend="auto" will run); the record shows the winner's rate and the
    # full measured ordering. Off-TPU the interpret-mode kernel is excluded
    # for the same reason as above (explicit candidate lists don't pin the
    # dispatch cache — the record is a measurement, not a winner override).
    from marlin_tpu.ops import tile_family
    from marlin_tpu.parallel import autotune

    cands = None
    if not run_pallas:
        cands = [c for c in tile_family.bsr_candidates(
            bs, bsr.nnzb, p, 4) if c != "pallas"]
    ranking = autotune.tune_bsr(bsr, b, candidates=cands, reps=2)
    win, sec = ranking[0]
    order = ", ".join(f"{nm} {s * 1e3:.1f}ms" for nm, s in ranking)
    record(f"bsr_{n}x{n}_bd{block_density}_family", flops / sec / 1e9,
           "GFLOP/s", f"winner {win} of [{order}]; nnzb={nnzb}, bs={bs}, "
           f"p={p} (backend='auto' dispatches this)")


def config_nn(m=262_144, d=784, hidden=1024, classes=10, batch=8192,
              iters=50):
    """MLP training throughput — the reference's flagship iterative workload
    (examples/NeuralNetwork.scala; MNIST-shaped synthetic data at 4× MNIST's
    row count so sampling stride matters). One jitted SPMD step per iteration,
    weights resident on device; the recorded figure is steps/s and the
    model-FLOP rate (3 matmul passes per layer per step: fwd + two grad)."""
    import jax
    import jax.numpy as jnp

    import marlin_tpu as mt
    from marlin_tpu.ml import NeuralNetwork

    mesh = mt.create_mesh()
    data = mt.DenseVecMatrix.random(0, m, d, mesh=mesh)
    labels = np.arange(m) % classes
    nn = NeuralNetwork(input_dim=d, hidden_dim=hidden, output_dim=classes)
    # warm-up (compile) outside the timed region
    params, _ = nn.train(data, labels, iterations=2, batch_size=batch)
    t0 = time.perf_counter()
    params, losses = nn.train(data, labels, iterations=iters,
                              batch_size=batch, params=params)
    dt = time.perf_counter() - t0
    assert np.isfinite(losses[-1])
    layer_flops = 2 * batch * (d * hidden + hidden * classes)
    steps_per_s = iters / dt
    record(f"nn_{m}x{d}_h{hidden}_b{batch}", steps_per_s, "steps/s",
           f"{3 * layer_flops * steps_per_s / 1e9:.0f} GFLOP/s model, "
           f"loss {losses[-1]:.4f}")


def config_lct(seq=32768, d_model=256, heads=2, layers=2, steps=3,
               remat=False, loss_chunk=None, name=None, attn="ring",
               compute_dtype=None, mlp_chunk=None, offload_residuals=False):
    """Long-context LM training throughput: one 32k-token causal stream,
    flash ring attention (dh=128 -> MXU tiles), Adam, full backward through
    the sequence-parallel attention (recompute VJP). No reference analog —
    this is the long-context mandate's training headline."""
    import numpy as np

    import marlin_tpu as mt
    from marlin_tpu.models import TransformerLM

    mesh = mt.create_mesh()
    rng = np.random.default_rng(0)
    vocab = 512
    tokens = rng.integers(0, vocab, seq).astype(np.int32)
    lm = TransformerLM(vocab=vocab, d_model=d_model, heads=heads,
                       layers=layers, attn=attn, remat=remat,
                       loss_chunk=loss_chunk, compute_dtype=compute_dtype,
                       mlp_chunk=mlp_chunk,
                       offload_residuals=offload_residuals)
    params, _ = lm.train(tokens, steps=1, mesh=mesh)  # compile
    t0 = time.perf_counter()
    params, losses = lm.train(tokens, steps=steps, mesh=mesh, params=params)
    dt = time.perf_counter() - t0
    assert np.isfinite(losses[-1])
    knobs = "+remat" if remat else ""
    knobs += f"+loss_chunk{loss_chunk}" if loss_chunk else ""
    knobs += f"+{compute_dtype}" if compute_dtype else ""
    record(name or f"lct_{seq}tok_d{d_model}_h{heads}_l{layers}",
           seq * steps / dt / 1e3, "ktok/s",
           f"{steps} steps in {dt:.1f} s, loss {losses[-1]:.3f}, "
           f"fwd+bwd through flash ring attention{knobs}")


def config_moe(seq=32768, d_model=256, heads=2, layers=2, n_experts=8,
               steps=3):
    """Mixture-of-experts LM training throughput at the lct shape: same
    32k-token stream and flash ring attention, the FFN replaced by 8 experts
    with GShard top-2 capacity routing (grouped — routing memory linear in
    seq) and the Switch aux in the loss. The comparison row for
    lct_32768tok: what expert routing costs at equal d_model (the MoE win is
    CAPACITY — 8x FFN params at ~2x FFN FLOPs — not step time). No
    reference analog (docs/parallelism.md "Expert parallelism")."""
    import numpy as np

    import marlin_tpu as mt
    from marlin_tpu.models import TransformerLM

    mesh = mt.create_mesh()
    rng = np.random.default_rng(0)
    vocab = 512
    tokens = rng.integers(0, vocab, seq).astype(np.int32)
    lm = TransformerLM(vocab=vocab, d_model=d_model, heads=heads,
                       layers=layers, remat=True, loss_chunk=2048,
                       n_experts=n_experts)
    params, _ = lm.train(tokens, steps=1, mesh=mesh)  # compile
    t0 = time.perf_counter()
    params, losses = lm.train(tokens, steps=steps, mesh=mesh, params=params)
    dt = time.perf_counter() - t0
    assert np.isfinite(losses[-1])
    record(f"moe_{seq}tok_e{n_experts}_top2_d{d_model}_l{layers}",
           seq * steps / dt / 1e3, "ktok/s",
           f"{steps} steps in {dt:.1f} s, loss {losses[-1]:.3f}, "
           f"{n_experts} experts/layer, grouped GShard routing + aux")


def config_attn_long():
    """Pure-attention long-context point: one causal flash forward at 256k+
    tokens (MARLIN_BENCH_ATTN_SEQ scales; O(S²) compute so reps stay low)."""
    seq = int(os.environ.get("MARLIN_BENCH_ATTN_SEQ", 262144))
    # reps amortize the sync; once a single forward is seconds (O(S²)) that
    # amortization buys nothing — drop to 1 rep past 256k
    config_attention(seq=seq, variants=(("flash", "high"),
                                        ("flash", "default")),
                     reps=3 if seq <= 262144 else 1)


def config_lct_long():
    """The marquee long-context run: the longest causal stream one 16 GB v5e
    trains end-to-end (ring flash attention + per-block remat + chunked LM
    head). HBM budget at the defaults (seq S=256k, d=256, L=2, f32):
    residual checkpoints ~L*S*d*4 = 512 MB, block recompute peak ~S*d_ff*4
    = 1 GB, head chunk ~MBs, params+Adam ~MBs — see docs/parallelism.md.
    MARLIN_BENCH_LCT_SEQ scales it up (524288, 1048576) to find the cliff."""
    seq = int(os.environ.get("MARLIN_BENCH_LCT_SEQ", 262144))
    # flash pinned (auto would pick it on TPU anyway): the Pallas forward +
    # two-pass Pallas backward is the only memory-feasible path up here.
    # MARLIN_BENCH_LCT_DTYPE=bfloat16 selects the mixed-precision path —
    # REQUIRED at 1M tokens (f32 needs 22 GiB; bf16 fits — AOT_MEMORY.json)
    cd = os.environ.get("MARLIN_BENCH_LCT_DTYPE") or None
    mc = int(os.environ.get("MARLIN_BENCH_LCT_MLP_CHUNK", 0)) or None
    remat, lc, off = True, 16384, False
    if os.environ.get("MARLIN_BENCH_LCT_PLAN") == "1":
        # let the planner pick the knobs from the compiler's own memory
        # accounting (models/planner.py) instead of the hand-set defaults —
        # costs one AOT compile per probed rung (~1 min each at 1M tokens),
        # which is why it is opt-in
        from marlin_tpu.models import TransformerLM, plan_context

        base = TransformerLM(vocab=512, d_model=256, heads=2, layers=2,
                             attn="ring_flash")
        plan = plan_context(seq, base)
        print(f"[lct_long] planner: {plan.describe()}", flush=True)
        m = plan.model
        remat, lc, mc, cd, off = m.remat, m.loss_chunk, m.mlp_chunk, \
            m.compute_dtype, m.offload_residuals
    suffix = f"_{cd}" if cd else ""
    config_lct(seq=seq, steps=2, remat=remat, loss_chunk=lc,
               name=f"lct_long_{seq}tok_d256_h2_l2{suffix}",
               attn="ring_flash", compute_dtype=cd, mlp_chunk=mc,
               offload_residuals=off)


def config_decode(d_model=512, heads=8, layers=4, vocab=4096,
                  prompt_len=512, steps_a=64, steps_b=320):
    """KV-cached autoregressive decode: prefill vs per-token split, plus the
    traced-temperature no-recompile guarantee (round-3 verdict #7). Two step
    counts isolate the per-token cost (total = prefill + steps x per_token);
    a temperature sweep afterward must not grow the jit cache."""
    import jax
    import numpy as np

    import marlin_tpu as mt  # noqa: F401  (mesh/env init side effects)
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.models.transformer import lm_generate

    lm = TransformerLM(vocab=vocab, d_model=d_model, heads=heads,
                       layers=layers, seed=0)
    params = lm.init_params()
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, prompt_len).astype(np.int32)
    key = jax.random.key(0)
    max_len = prompt_len + steps_b

    def run(steps, temperature=0.7):
        out = lm_generate(params, prompt, key, heads=heads, max_len=max_len,
                          steps=steps, temperature=temperature)
        jax.block_until_ready(out)
        return out

    run(steps_a), run(steps_b)  # compile both step counts
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        run(steps_a)
    ta = (time.perf_counter() - t0) / reps
    t0 = time.perf_counter()
    for _ in range(reps):
        run(steps_b)
    tb = (time.perf_counter() - t0) / reps
    per_tok = (tb - ta) / (steps_b - steps_a)
    prefill_s = max(ta - steps_a * per_tok, 1e-9)

    n_compiled = lm_generate._cache_size()
    for t in (0.0, 0.3, 1.3):
        run(steps_a, temperature=t)
    assert lm_generate._cache_size() == n_compiled, \
        "temperature sweep recompiled lm_generate"

    record(f"decode_d{d_model}_h{heads}_l{layers}_v{vocab}", 1.0 / per_tok,
           "tok/s",
           f"decode {per_tok * 1e3:.2f} ms/tok; prefill {prompt_len} tok in "
           f"{prefill_s * 1e3:.0f} ms ({prompt_len / prefill_s / 1e3:.1f} "
           f"ktok/s); no recompile across temperatures")

    # batch-decode throughput: the serving shape — per-step matmuls become
    # (B, d) @ (d, d) MXU work, so tok/s should scale far better than
    # linearly in cost. Short prompts (dense prefill, no flash dependency).
    from marlin_tpu.models.transformer import lm_generate_batch

    for bsz in (8, 64):
        bp = rng.integers(0, vocab, (bsz, prompt_len)).astype(np.int32)
        lens = np.full(bsz, prompt_len, np.int32)

        def run_b():
            out = lm_generate_batch(params, bp, lens, key, heads=heads,
                                    max_len=prompt_len + steps_a,
                                    steps=steps_a, temperature=0.7)
            jax.block_until_ready(out)

        run_b()  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            run_b()
        tb_ = (time.perf_counter() - t0) / 3
        record(f"decode_batch{bsz}", bsz * steps_a / tb_, "tok/s",
               f"{bsz} sequences decoded together, {steps_a} steps each; "
               f"{tb_ * 1e3 / steps_a:.2f} ms per batched step")

    # prompt-length sweep (round-4 verdict #3): past _PREFILL_FLASH_MIN the
    # prefill runs the flash kernel, so long-document prompts neither OOM
    # (linear score memory) nor fall off a throughput cliff. steps is tiny so
    # the measurement is prefill-dominated; per_tok from above removes the
    # decode tail. MARLIN_BENCH_DECODE_SWEEP=0 skips the sweep — the recovery
    # runner sets it when the Mosaic flash smoke failed, keeping a flash
    # compile failure out of the otherwise flash-free decode config.
    if os.environ.get("MARLIN_BENCH_DECODE_SWEEP", "1") == "0":
        return
    sweep_steps = 8
    for plen in (4096, 16384, 65536):
        pr = rng.integers(0, vocab, plen).astype(np.int32)

        def run_p(temperature=0.7):
            out = lm_generate(params, pr, key, heads=heads,
                              max_len=plen + sweep_steps, steps=sweep_steps,
                              temperature=temperature)
            jax.block_until_ready(out)

        run_p()  # compile
        t0 = time.perf_counter()
        for _ in range(3):
            run_p()
        tp = (time.perf_counter() - t0) / 3
        pf = max(tp - sweep_steps * per_tok, 1e-9)
        record(f"decode_prefill_p{plen}", plen / pf / 1e3, "ktok/s",
               f"flash prefill ({plen} >= 2048 threshold): {pf * 1e3:.0f} ms "
               f"for the prompt; linear score memory (AOT-asserted)")


def config_serve(d_model=128, heads=8, layers=4, vocab=256):
    """Offered-load sweep through the serving engine (marlin_tpu/serving/):
    submitters inject Poisson-ish open-loop traffic at each offered rate;
    reported per rate are achieved tokens/s and p50/p99 end-to-end + TTFT
    latency (submit -> Result / first token). Env control,
    MARLIN_BENCH_PREFETCH-style:
    MARLIN_BENCH_SERVE_RATES (req/s list, default "4,16,64"),
    MARLIN_BENCH_SERVE_N (requests per rate, default 64),
    MARLIN_BENCH_SERVE_BATCH (slot width, default 8),
    MARLIN_BENCH_SERVE_STEPS (decode-steps range "lo,hi", default "4,32" —
    ragged output lengths, the traffic continuous batching exists for;
    rows retire at their requested steps),
    MARLIN_BENCH_SERVE_WARMUP=0 skips the per-bucket pre-compile (the
    first-request-pays-the-compile A/B),
    MARLIN_BENCH_SERVE_PREFIX_LEN=N (0 = off, the default) prepends a
    shared N-token system prompt to every request — the prefix-cache
    workload (records get a `_prefix` suffix; the acceptance bar is
    prefix-cache hits > 0); the per-rate detail carries the hit counts,
    MARLIN_BENCH_SERVE_ROUTER=N (0 = off, the default) serves each rate
    through a Router over N supervised engine replicas instead of one bare
    engine — the resilience-layer A/B (records get a `_router` suffix;
    the acceptance bar is routed tok/s within 5% of the single-engine
    baseline at the top rate; router records carry the fleet
    `router_prefix_hit_rate` as a gated ride-along).

    MARLIN_BENCH_REPS=N (default 1) repeats every rate N times and records
    the median rep — serve numbers sample a live multi-threaded engine, so
    one rep is one draw of host scheduling noise. The sweep also emits a
    `serve_control*` record (a fixed pure-numpy matmul loop): it moves only
    when the HOST moved, and tools/bench_compare.py downgrades serve
    regressions that slid with it to warnings. The model
    (d_model=128, heads=8, layers=4) is sized so decode COMPUTE is
    non-trivial relative to dispatch — the serving regime; at toy sizes the
    sweep measures Python/dispatch overhead.

    Observability ride-along (docs/observability.md): a /metrics endpoint
    (MARLIN_BENCH_OBS_PORT, default ephemeral) is scraped DURING the first
    rate's live serve, every serve record lands in a JSONL
    (MARLIN_BENCH_SERVE_EVENTS, default under $TMPDIR) with request trace
    ids, and a `serve_obs` record reports scrape families + trace join —
    the proof the layer sees traffic without steering it."""
    import urllib.request

    import jax  # noqa: F401  (backend init before threads)

    import marlin_tpu as mt  # noqa: F401
    from marlin_tpu import obs
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.obs import collectors
    from marlin_tpu.serving import Request, Router, ServeEngine, percentile
    from marlin_tpu.utils.tracing import EventLog, set_default_event_log

    rates = [float(r) for r in os.environ.get(
        "MARLIN_BENCH_SERVE_RATES", "4,16,64").split(",")]
    n_req = int(os.environ.get("MARLIN_BENCH_SERVE_N", 64))
    max_batch = int(os.environ.get("MARLIN_BENCH_SERVE_BATCH", 8))
    warmup = os.environ.get("MARLIN_BENCH_SERVE_WARMUP", "1") != "0"
    # decode-kernel A/B control: "" = the config default ('auto'),
    # "gather"/"pallas" force a backend and tag every record key with _k…
    # so both legs coexist in BENCH_ALL.json
    decode_kernel = os.environ.get("MARLIN_BENCH_DECODE_KERNEL", "")
    prefix_len = int(os.environ.get("MARLIN_BENCH_SERVE_PREFIX_LEN", "0"))
    if prefix_len > 240:
        # prompts must leave the per-request tail (8..) room inside the
        # largest (256, ...) bucket — clamp rather than die on the first
        # submit with a numpy low>=high error
        log(f"MARLIN_BENCH_SERVE_PREFIX_LEN={prefix_len} clamped to 240 "
            f"(tails need room inside the 256-token bucket)")
        prefix_len = 240
    router_n = int(os.environ.get("MARLIN_BENCH_SERVE_ROUTER", "0"))
    suffix = (("_prefix" if prefix_len else "")
              + ("_router" if router_n else "")
              + (f"_k{decode_kernel}" if decode_kernel else ""))
    steps_lo, steps_hi = (int(v) for v in os.environ.get(
        "MARLIN_BENCH_SERVE_STEPS", "4,32").split(","))
    buckets = ((64, 32), (256, 32))
    lm = TransformerLM(vocab=vocab, d_model=d_model, heads=heads,
                      layers=layers, seed=0)
    params = lm.init_params()
    rng = np.random.default_rng(0)
    # the shared system prompt for the prefix-cache workload: fixed tokens,
    # page-aligned-friendly length, identical across requests and sweeps
    prefix = (np.arange(prefix_len) * 7 % vocab).astype(np.int32)

    events_path = os.environ.get("MARLIN_BENCH_SERVE_EVENTS") or os.path.join(
        tempfile.gettempdir(), f"marlin_serve_events{suffix}.jsonl")
    for rot in ("", ".1", ".2"):  # fresh stream per sweep
        if os.path.exists(events_path + rot):
            os.remove(events_path + rot)
    elog = EventLog(events_path)
    prev_log = set_default_event_log(elog)
    srv = obs.MetricsServer(port=int(os.environ.get("MARLIN_BENCH_OBS_PORT",
                                                    "0")))
    obs_port = srv.start()  # installs compile + device-memory collectors
    scrape = ""
    mem_during: dict = {}

    def make_engine():
        return ServeEngine(params, heads, buckets=buckets,
                           max_batch=max_batch, max_wait_ms=5.0,
                           queue_depth=4 * n_req,
                           decode_kernel=decode_kernel or None)

    def run_rate(rate):
        nonlocal scrape
        if router_n:
            # the resilience A/B: supervised replicas behind the router,
            # same total offered load (admission capacity scales with N —
            # per-replica queues still bound overload)
            eng = Router(make_engine, replicas=router_n, warmup=warmup)
        else:
            eng = make_engine()
        try:
            if warmup and not router_n:
                eng.warmup()
            gaps = rng.exponential(1.0 / rate, n_req)
            handles, t_start = [], time.perf_counter()
            for i in range(n_req):
                if i:  # inter-arrival gaps only BETWEEN submits: a trailing
                    # sleep after the last one would deflate tok/s at low
                    # rates (no request is outstanding during it)
                    time.sleep(gaps[i - 1])
                plen = int(rng.integers(8, min(192, 256 - prefix_len)))
                prompt = rng.integers(0, vocab, plen).astype(np.int32)
                if prefix_len:
                    # the shared-prefix shape: one system prompt + a short
                    # per-request tail (the prefix cache should prefill the
                    # system prompt once per pool lifetime)
                    prompt = np.concatenate([prefix, prompt])
                handles.append(eng.submit(Request(
                    prompt=prompt,
                    steps=int(rng.integers(steps_lo, steps_hi + 1)))))
            scraper = None
            if not scrape:
                # scrape DURING the live serve (requests still in flight at
                # the first offered rate): the endpoint must show traffic
                # while it happens, not post-hoc aggregates. Off-thread so a
                # slow scrape never inflates the measured span — the tok/s
                # this sweep records is the passivity evidence.
                def _scrape_live():
                    nonlocal scrape, mem_during
                    collectors.log_device_memory(elog)  # mem timeline
                    try:
                        # the HBM ledger's mid-serve reconcile: taken while
                        # the KV slab and programs are still resident, so
                        # the serve_mem record attributes live bytes, not
                        # the post-close remainder
                        from marlin_tpu.obs import memledger
                        mem_during = memledger.reconcile()
                    except Exception:
                        pass
                    try:
                        scrape = urllib.request.urlopen(
                            f"http://127.0.0.1:{obs_port}/metrics",
                            timeout=10).read().decode()
                    except Exception:
                        pass  # next rate retries; the record shows 0/7
                scraper = threading.Thread(target=_scrape_live, daemon=True)
                scraper.start()
            eng.drain()
            span = time.perf_counter() - t_start
        finally:
            eng.close()
        if scraper is not None:
            scraper.join(timeout=15.0)
        results = [h.result(timeout=0) for h in handles]
        ok = [r for r in results if r.ok]
        lat = [r.metrics["total_s"] for r in ok]
        ttft = [r.metrics["ttft_s"] for r in ok
                if r.metrics.get("ttft_s") is not None]
        snap = eng.snapshot() if router_n else eng.metrics.snapshot()
        toks = sum(r.tokens.size - len(h.request.prompt)
                   for h, r in zip(handles, results) if r.ok)
        # a fully-shed load point (admission rejecting everything, chaos
        # faults) is a degraded data point, not a sweep abort
        ms = lambda xs, q: (  # noqa: E731
            f"{percentile(xs, q) * 1e3:.0f}" if xs else "n/a")
        hits, misses = snap.get("prefix_hits", 0), \
            snap.get("prefix_misses", 0)
        sched = (f"paged, {snap['steps']} decode steps, "
                 f"prefix-cache {hits} hit / {misses} miss, "
                 f"cache-resident pages {snap.get('pages_used', 0)}"
                 f"/{snap.get('pages_total', 0)}")
        extra = None
        if router_n:
            # the router observability satellite (ISSUE 12): the merged
            # snapshot spans rotated-out replicas too, so the hit rate is
            # the fleet's — the prefix-affinity acceptance bar reads it
            hr = snap.get("prefix_hit_rate")
            sched = (f"{router_n}-replica supervised router "
                     f"({snap['retries']} retries, "
                     f"{snap.get('migrated_in', 0)} adopted, "
                     f"prefix-hit-rate "
                     f"{hr if hr is not None else 'n/a'}), " + sched)
            extra = {"router_prefix_hit_rate": hr}
        occ = snap.get("occupancy_mean", "n/a")
        detail = (f"{len(ok)}/{n_req} ok at {rate:g} req/s offered; p50 "
                  f"{ms(lat, 50)} ms / p99 {ms(lat, 99)} ms latency; ttft "
                  f"p50 {ms(ttft, 50)} ms / p99 {ms(ttft, 99)} ms; "
                  f"occupancy {occ}, {sched}, "
                  f"warmup={'on' if warmup else 'off'}")
        return toks / span, detail, extra

    # host-drift control (ISSUE 12): a fixed pure-numpy workload no serving
    # change can touch — when IT moves between BASE and NEW, the host was
    # noisy and bench_compare downgrades same-direction serve regressions
    # to warnings instead of failing the gate on machine weather
    def run_control():
        rng_c = np.random.default_rng(12345)
        a = rng_c.standard_normal((256, 256))
        reps_c = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 0.25:
            a = a @ a.T
            a *= 1e-3 / max(1e-9, float(abs(a).max()))
            reps_c += 1
        span = time.perf_counter() - t0
        return 2 * 256**3 * reps_c / span / 1e9

    # MARLIN_BENCH_REPS (ISSUE 12): median-of-N per offered rate — the
    # serve legs measure a live multi-threaded engine on a shared host, so
    # a single rep is one sample of the machine's mood; the median rep's
    # (value, detail) pair is recorded whole to keep the numbers coherent
    bench_reps = max(1, int(os.environ.get("MARLIN_BENCH_REPS", "1")))

    try:
        for rate in rates:
            runs = sorted((run_rate(rate) for _ in range(bench_reps)),
                          key=lambda t: t[0])
            val, detail, extra = runs[len(runs) // 2]
            if bench_reps > 1:
                detail += f"; median of {bench_reps} reps"
            # the prefix/router/kernel legs keep their own record keys so
            # they coexist in BENCH_ALL.json (merge keyed by config)
            record(f"serve_load{rate:g}" + suffix, val, "tok/s", detail,
                   extra=extra)
        ctrl = sorted(run_control() for _ in range(bench_reps))
        record("serve_control" + suffix,
               ctrl[len(ctrl) // 2], "GFLOP/s",
               "untouched-control sentinel: fixed 256x256 numpy matmul "
               "loop, no marlin code on the path — drift here is host "
               "noise, and the gate warns instead of failing when serve "
               "records move WITH it",
               extra={"control": True})
        # ---- decode-program roofline: the serve sweep's utilization record
        # (ISSUE 6 acceptance: BENCH rounds track utilization, not just
        # tok/s). The cost model came from warmup's capture, the timings
        # from the engines' live decode steps across all rates.
        from marlin_tpu.obs import perf as obs_perf

        decode_prog = "lm_decode_paged"
        decode_rows = [r for r in obs_perf.get_program_costs().rows()
                       if r["program"] == decode_prog and r["calls"]
                       and r["roofline_frac"] is not None]
        if decode_rows:
            r = max(decode_rows, key=lambda r: r["calls"])
            # frac can be non-None while achieved/peak_flops are (bandwidth
            # roofline, bytes-only cost model) — format each defensively
            ach = (f"{r['achieved_flops_per_s'] / 1e9:.2f} GFLOP/s"
                   if r["achieved_flops_per_s"] else "n/a")
            peak = (f"peak {r['peak_flops'] / 1e12:.1f} TFLOP/s"
                    if r["peak_flops"] else "the bandwidth roofline")
            record("serve_decode_roofline" + suffix,
                   r["roofline_frac"], "frac",
                   f"{decode_prog}[{r['key']}]: {ach} achieved over "
                   f"{r['calls']} dispatches vs {peak} "
                   f"(marlin_program_roofline_frac live on /metrics)",
                   extra={"roofline_frac": round(r["roofline_frac"], 4)})
    finally:
        # a mid-sweep failure must not leak the default log / endpoint into
        # the rest of the bench process (main() catches and keeps sweeping)
        srv.close()
        set_default_event_log(prev_log)
        elog.close()

    # ---- observability acceptance record: scrape families + trace join
    want = ("marlin_serve_submitted_total", "marlin_serve_queue_depth",
            "marlin_serve_slot_occupancy", "marlin_serve_kv_inflight_bytes",
            "marlin_compile_total", "marlin_prefetch_chunks_total",
            "marlin_device_memory_bytes_in_use",
            "marlin_program_roofline_frac",
            # the HBM-ledger attribution families (obs/memledger.py) ride
            # the same scrape: TYPE lines render even before any backend
            # sample lands, so the check holds on CPU too
            "marlin_mem_registered_bytes", "marlin_mem_live_bytes",
            "marlin_mem_unattributed_bytes",
            "marlin_serve_kv_pages_total", "marlin_serve_kv_pages_used",
            "marlin_serve_prefix_cache_total")
    if router_n:
        # the resilience families ride only when the router/supervisors ran
        want += ("marlin_serve_retries_total", "marlin_serve_restarts_total",
                 "marlin_serve_replica_state")
    got = [n for n in want if f"# TYPE {n} " in scrape]
    # same "trace-joined" definition as python -m marlin_tpu.obs.report
    from marlin_tpu.obs.report import trace_join
    joined, total = trace_join(elog.read(include_rotated=True))
    trace_note = (f"{joined}/{total} requests trace-joined"
                  if total else "no serve events recorded")
    record("serve_obs" + suffix, float(len(got)),
           "families",
           f"live /metrics scrape during serve carried {len(got)}/{len(want)}"
           f" series ({', '.join(got)}); {trace_note}; events at "
           f"{events_path} (analyze: python -m marlin_tpu.obs.report)")

    # ---- memory-attribution acceptance record (HBM ledger,
    # docs/observability.md "Memory attribution"): the mid-serve reconcile
    # taken by the scrape thread is the evidence — per-component
    # attribution while the slab was resident, the unattributed fraction
    # ("n/a" without backend memory_stats, i.e. CPU), and the
    # calibrated-vs-raw admission headroom read from AOT_MEMORY.json's
    # serve_buckets table. Value = marlin_mem_* families on the live
    # scrape, so the record gates (unit is not informational).
    from marlin_tpu.obs import memledger

    mem_want = ("marlin_mem_registered_bytes", "marlin_mem_live_bytes",
                "marlin_mem_unattributed_bytes")
    mem_got = [n for n in mem_want if f"# TYPE {n} " in scrape]
    rec = mem_during or memledger.reconcile()
    frac = rec.get("unattributed_frac")
    comp = rec.get("components") or {}
    comp_note = (", ".join(f"{k} {v / 1e6:.1f}MB"
                           for k, v in sorted(comp.items()))
                 or "no live ledger entries at scrape time")
    ratios = [r["calibration"] for r in memledger.ratio_table()
              if r.get("calibration")]
    headroom = f"{max(ratios):.2f}" if ratios else "n/a"
    record("serve_mem" + suffix, float(len(mem_got)), "families",
           f"{len(mem_got)}/{len(mem_want)} marlin_mem_* families on the "
           f"live scrape; unattributed frac "
           f"{frac if frac is not None else 'n/a'}; components: "
           f"{comp_note}; calib-headroom {headroom}")


def config_serve_als(d_model=64, heads=4, layers=2, vocab=256):
    """BucketProgram serving legs (serving/programs/, ISSUE 18): (a) ALS
    recommendation scoring alone through the engine spine — achieved QPS
    and p50/p99 submit→Result latency against device-resident factors
    (`serve_als`) — and (b) the mixed-traffic leg: the same open-loop LM
    stream run bare, then again with an equal ALS stream interleaved on the
    SAME engine; `serve_mixed_lm` records the mixed run's LM tokens/s with
    the LM-only control and the mixed/solo ratio in the detail (acceptance:
    within 5% — co-resident one-shot programs must not tax LM decode).

    MARLIN_BENCH_SERVE_ALS_N (ALS requests, default 256),
    MARLIN_BENCH_SERVE_ALS_SHAPE ("users,items,rank", default
    "512,256,16"), MARLIN_BENCH_SERVE_MIX_N (LM requests per mixed leg,
    default 32) size the legs; MARLIN_BENCH_REPS medians the mixed pair."""
    import jax  # noqa: F401  (backend init before threads)

    from marlin_tpu.models import TransformerLM
    from marlin_tpu.serving import (ALSScoreProgram, Request, ServeEngine,
                                    percentile)

    n_als = int(os.environ.get("MARLIN_BENCH_SERVE_ALS_N", 256))
    users, items, rank = (int(v) for v in os.environ.get(
        "MARLIN_BENCH_SERVE_ALS_SHAPE", "512,256,16").split(","))
    n_lm = int(os.environ.get("MARLIN_BENCH_SERVE_MIX_N", 32))
    reps = max(1, int(os.environ.get("MARLIN_BENCH_REPS", "1")))
    rng = np.random.default_rng(0)
    uf = rng.standard_normal((users, rank)).astype(np.float32)
    pf = rng.standard_normal((items, rank)).astype(np.float32)
    lm = TransformerLM(vocab=vocab, d_model=d_model, heads=heads,
                      layers=layers, seed=0)
    params = lm.init_params()
    buckets = ((64, 32),)

    def make_engine():
        eng = ServeEngine(params, heads, buckets=buckets, max_batch=8,
                          max_wait_ms=1.0, queue_depth=4 * (n_als + n_lm),
                          programs=[ALSScoreProgram((uf, pf))])
        eng.warmup()
        return eng

    def als_requests(n):
        return [Request(program="als",
                        payload={"user": int(rng.integers(0, users)),
                                 "k": 8})
                for _ in range(n)]

    def lm_requests(n):
        return [Request(prompt=rng.integers(0, vocab, int(
                    rng.integers(8, 48))).astype(np.int32),
                        steps=int(rng.integers(4, 16)))
                for _ in range(n)]

    # ---- leg (a): ALS alone — QPS + latency percentiles
    eng = make_engine()
    try:
        t0 = time.perf_counter()
        handles = [eng.submit(r) for r in als_requests(n_als)]
        eng.drain()
        span = time.perf_counter() - t0
        results = [h.result(timeout=0) for h in handles]
    finally:
        eng.close()
    ok = [r for r in results if r.ok]
    lat = sorted(r.metrics["total_s"] for r in ok)
    ms = lambda q: (f"{percentile(lat, q) * 1e3:.1f}"  # noqa: E731
                    if lat else "n/a")
    record("serve_als", len(ok) / span, "req/s",
           f"{len(ok)}/{n_als} ok; top-8 of {items} items, rank {rank}, "
           f"{users} users resident; p50 {ms(50)} ms / p99 {ms(99)} ms "
           f"submit-to-result")

    # ---- leg (b): the mixed-traffic bar — LM tok/s solo vs with an equal
    # ALS stream co-resident on the same engine
    def run_lm(mixed):
        eng = make_engine()
        try:
            reqs = lm_requests(n_lm)
            extra = als_requests(n_lm) if mixed else []
            t0 = time.perf_counter()
            handles = [eng.submit(r) for r in reqs]
            ehandles = [eng.submit(r) for r in extra]
            eng.drain()
            span = time.perf_counter() - t0
            results = [h.result(timeout=0) for h in handles]
            eok = sum(h.result(timeout=0).ok for h in ehandles)
        finally:
            eng.close()
        toks = sum(r.tokens.size - len(q.prompt)
                   for q, r in zip(reqs, results) if r.ok)
        return toks / span, sum(r.ok for r in results), eok

    solo = sorted(run_lm(False)[0] for _ in range(reps))[reps // 2]
    mixed_runs = sorted((run_lm(True) for _ in range(reps)),
                        key=lambda t: t[0])
    mixed_toks, lm_ok, als_ok = mixed_runs[reps // 2]
    ratio = mixed_toks / solo if solo else 0.0
    record("serve_mixed_lm", mixed_toks, "tok/s",
           f"LM decode under mixed LM+ALS load: {lm_ok}/{n_lm} LM ok with "
           f"{als_ok}/{n_lm} ALS ok co-resident; LM-only control "
           f"{solo:.1f} tok/s, mixed/solo ratio {ratio:.3f} "
           f"(bar: >= 0.95)" + (f"; median of {reps} reps"
                                if reps > 1 else ""),
           extra={"mixed_solo_ratio": round(ratio, 4)})


def config_serve_slo(d_model=64, heads=4, layers=2, vocab=256):
    """SLO-engine acceptance leg (docs/observability.md "Serving SLOs"):
    the same open-loop serve run twice — leg A with `serve_slo` objectives
    configured (generous targets, so the engine evaluates but never
    breaches) and leg B plain — and records (a) the `marlin_slo_*`
    families carried by a live /metrics scrape plus the `/debug/slo`
    payload DURING leg A's serve, and (b) passivity: an
    evaluating-but-quiet SLO engine must cost <= 2% tok/s vs the plain
    engine (the A/B lands as `serve_slo_passivity`; tools/Makefile's
    obs-gate reads both through bench_compare --only serve_).

    MARLIN_BENCH_SERVE_SLO_N (requests per leg, default 48) and
    MARLIN_BENCH_SERVE_SLO_RATE (req/s, default 32) size the legs."""
    import urllib.request

    import jax  # noqa: F401  (backend init before threads)

    import marlin_tpu as mt
    from marlin_tpu import obs
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.serving import Request, ServeEngine

    n_req = int(os.environ.get("MARLIN_BENCH_SERVE_SLO_N", 48))
    rate = float(os.environ.get("MARLIN_BENCH_SERVE_SLO_RATE", 32))
    buckets = ((64, 32),)
    params = TransformerLM(vocab=vocab, d_model=d_model, heads=heads,
                           layers=layers, seed=0).init_params()
    rng = np.random.default_rng(0)
    # generous targets: the leg proves evaluation cost + exposition, not
    # breach handling (tests/test_slo.py owns the breach state machine)
    slo_cfg = (
        {"name": "ttft", "metric": "p95:marlin_serve_ttft_seconds",
         "target": 60.0, "window_s": 600.0},
        {"name": "avail",
         "metric": "ratio:marlin_serve_requests_total{status=ok}"
                   "/marlin_serve_requests_total",
         "target": 0.5, "window_s": 600.0},
    )

    srv = obs.MetricsServer(port=int(os.environ.get("MARLIN_BENCH_OBS_PORT",
                                                    "0")))
    obs_port = srv.start()
    scrape, slo_json = "", ""

    def run_leg(with_slo):
        nonlocal scrape, slo_json
        ctx = (mt.config_context(serve_slo=slo_cfg,
                                 serve_slo_eval_interval_s=0.25,
                                 serve_ts_bucket_s=1.0)
               if with_slo else contextlib.nullcontext())
        with ctx:
            eng = ServeEngine(params, heads, buckets=buckets, max_batch=8,
                              max_wait_ms=5.0, queue_depth=4 * n_req)
        try:
            eng.warmup()
            gaps = rng.exponential(1.0 / rate, n_req)
            handles, t0 = [], time.perf_counter()
            for i in range(n_req):
                if i:
                    time.sleep(gaps[i - 1])
                plen = int(rng.integers(8, 48))
                handles.append(eng.submit(Request(
                    prompt=rng.integers(0, vocab, plen).astype(np.int32),
                    steps=int(rng.integers(4, 17)))))
            scraper = None
            if with_slo:
                def _scrape_live():  # off-thread: never inflates the span
                    nonlocal scrape, slo_json
                    try:
                        scrape = urllib.request.urlopen(
                            f"http://127.0.0.1:{obs_port}/metrics",
                            timeout=10).read().decode()
                        slo_json = urllib.request.urlopen(
                            f"http://127.0.0.1:{obs_port}/debug/slo",
                            timeout=10).read().decode()
                    except Exception:
                        pass  # the record shows 0/5 families
                scraper = threading.Thread(target=_scrape_live, daemon=True)
                scraper.start()
            eng.drain()
            span = time.perf_counter() - t0
        finally:
            eng.close()
        if scraper is not None:
            scraper.join(timeout=15.0)
        results = [h.result(timeout=0) for h in handles]
        toks = sum(r.tokens.size - len(h.request.prompt)
                   for h, r in zip(handles, results) if r.ok)
        return toks / span, sum(r.ok for r in results)

    try:
        # throwaway warm leg: the first engine of the process pays
        # first-render/threadpool costs that would land entirely on
        # whichever A/B leg runs first and masquerade as SLO overhead
        run_leg(False)
        # SLO leg next so the scrape catches it live; plain leg last
        tok_slo, ok_slo = run_leg(True)
        tok_plain, ok_plain = run_leg(False)
    finally:
        srv.close()

    want = ("marlin_slo_compliance", "marlin_slo_budget_remaining",
            "marlin_slo_burn_rate", "marlin_slo_breached",
            "marlin_slo_shed_total")
    got = [n for n in want if f"# TYPE {n} " in scrape]
    payload = {}
    try:
        payload = json.loads(slo_json)
    except Exception:
        pass
    scopes = payload.get("scopes") or []
    slo_names = sorted({o.get("slo") for s in scopes
                        for o in s.get("objectives", ())})
    record("serve_slo", float(len(got)), "families",
           f"live /metrics scrape during an SLO-evaluating serve carried "
           f"{len(got)}/{len(want)} marlin_slo_* series ({', '.join(got)}); "
           f"/debug/slo returned {len(scopes)} scope(s) with objectives "
           f"{slo_names}; {ok_slo}/{n_req} ok")
    delta = (tok_plain - tok_slo) / tok_plain if tok_plain > 0 else 0.0
    record("serve_slo_passivity", tok_slo, "tok/s",
           f"SLO leg {tok_slo:.1f} tok/s vs plain {tok_plain:.1f} tok/s "
           f"({delta:+.1%} cost; acceptance bar <= 2%); {ok_plain}/{n_req} "
           f"ok plain leg", extra={"plain_tok_s": round(tok_plain, 2),
                                   "delta_frac": round(delta, 4)})


def config_fleet(d_model=64, heads=4, layers=2, vocab=256):
    """Elastic-fleet acceptance leg (docs/serving.md "Elastic fleet"): a
    diurnal open-loop trace — quiet, burst, quiet — served twice through a
    Router. The elastic leg starts at ``serve_fleet_min_replicas`` and lets
    a FleetController scale on fleet-merged SLO burn; the static control
    leg serves the identical trace on a peak-sized fixed fleet. Records:

    - ``serve_fleet`` (elastic): value = fraction of the static fleet's
      replica-hours saved; detail carries dropped-request count, tail
      (p95) TTFT vs the SLO target, scale-event count, and
      ``replica-hours-saved F`` — the higher-is-better detail gate
      tools/bench_compare.py enforces under ``make -C tools fleet-gate``.
    - ``serve_fleet_static`` (control): the peak-sized fixed fleet's ok
      fraction + replica-hours, the denominator of the saving.

    MARLIN_BENCH_FLEET=0 skips the elastic leg (static control only).
    MARLIN_BENCH_FLEET_PHASES ("rate:count,…", default "4:12,40:160,2:32")
    shapes the trace, MARLIN_BENCH_FLEET_MAX (default 3) sizes the static
    fleet and the elastic ceiling, MARLIN_BENCH_FLEET_TTFT_SLO (seconds,
    default 0.3) sets the p95 TTFT objective the burn is computed from."""
    import jax  # noqa: F401  (backend init before threads)

    import marlin_tpu as mt
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.serving import (FleetController, Request, Router,
                                    ServeEngine, percentile)

    elastic = os.environ.get("MARLIN_BENCH_FLEET", "1") != "0"
    phases = [(float(r), int(c)) for r, c in
              (p.split(":") for p in os.environ.get(
                  "MARLIN_BENCH_FLEET_PHASES", "4:12,40:160,2:32")
               .split(","))]
    peak = int(os.environ.get("MARLIN_BENCH_FLEET_MAX", "3"))
    ttft_slo = float(os.environ.get("MARLIN_BENCH_FLEET_TTFT_SLO", "0.75"))
    n_req = sum(c for _, c in phases)
    buckets = ((64, 32),)
    params = TransformerLM(vocab=vocab, d_model=d_model, heads=heads,
                           layers=layers, seed=0).init_params()
    # the burn source: a tight p95 TTFT objective over a short window, so
    # the burst phase's queueing shows up as burn >> 1 within seconds and
    # the quiet phases decay back to slack
    slo_cfg = ({"name": "ttft", "metric": "p95:marlin_serve_ttft_seconds",
                "target": ttft_slo, "window_s": 60.0},)

    def make_engine():
        # the factory runs from controller action threads too — carry the
        # SLO config with it so scaled-out replicas evaluate burn as well
        # shedding off: the fleet experiment wants burn answered with
        # topology (scale events), not with admission-level degradation
        with mt.config_context(serve_slo=slo_cfg,
                               serve_slo_eval_interval_s=0.25,
                               serve_slo_fast_window_s=4.0,
                               serve_slo_shed=False,
                               serve_ts_bucket_s=1.0):
            # max_batch=2: batch SLOTS are the capacity unit, so a
            # scale-out adds real headroom even where replicas share
            # host compute (CPU CI) — the burst queues on slots, not FLOPs
            return ServeEngine(params, heads, buckets=buckets, max_batch=2,
                               max_wait_ms=5.0, queue_depth=4 * n_req)

    def run_trace(replicas, with_controller):
        rng = np.random.default_rng(7)  # identical trace both legs
        router = Router(make_engine, replicas=replicas, warmup=True)
        ctl = None
        # integrate replica-seconds off-thread at 50 ms so BOTH legs pay
        # the same accounting (the controller's own counter only advances
        # on its ticks, and the static leg has no controller at all)
        stop, acc = threading.Event(), {"rs": 0.0}

        def _integrate():
            last = time.perf_counter()
            while not stop.is_set():
                stop.wait(0.05)
                now = time.perf_counter()
                acc["rs"] += (now - last) * router.replica_count()
                last = now

        sampler = threading.Thread(target=_integrate, daemon=True)
        t0 = time.perf_counter()
        sampler.start()
        events = []
        try:
            if with_controller:
                ctl = FleetController(router, max_replicas=peak,
                                      eval_interval_s=0.25, out_burn=1.0,
                                      in_burn=0.25, hysteresis=1,
                                      cooldown_s=1.0, flap_window_s=6.0,
                                      action_timeout_s=120.0)
                ctl.start(poll_s=0.1)
            handles, submit_ts = [], []
            for rate, count in phases:
                gaps = rng.exponential(1.0 / rate, count)
                for i in range(count):
                    time.sleep(gaps[i])
                    plen = int(rng.integers(8, 48))
                    submit_ts.append(time.monotonic())
                    handles.append(router.submit(Request(
                        prompt=rng.integers(0, vocab, plen)
                        .astype(np.int32),
                        steps=int(rng.integers(24, 33)))))
            router.drain()
            span = time.perf_counter() - t0
            if ctl is not None:
                events = [r for r in ctl.payload()["history"]
                          if r["outcome"] == "ok"]
        finally:
            if ctl is not None:
                ctl.close()
            stop.set()
            sampler.join(timeout=5.0)
            router.close()
        results = [h.result(timeout=0) for h in handles]
        ok = [r for r in results if r.ok]
        ttft = [r.metrics["ttft_s"] for r in ok
                if r.metrics.get("ttft_s") is not None]
        # the converged tail: requests submitted after the last scale-out
        # landed (the fleet is at size for them) — the reaction transient
        # ahead of it is the price of elasticity, reported separately
        outs = [e["finished"] for e in events if e["action"] == "scale_out"]
        steady = [r.metrics["ttft_s"]
                  for t, r in zip(submit_ts, results)
                  if r.ok and r.metrics.get("ttft_s") is not None
                  and (not outs or t >= max(outs))] or ttft
        return {"ok": len(ok), "dropped": len(results) - len(ok),
                "span": span, "replica_seconds": acc["rs"],
                "ttft_p95_ms": (percentile(ttft, 95) * 1e3 if ttft
                                else 0.0),
                "ttft_steady_p95_ms": (percentile(steady, 95) * 1e3
                                       if steady else 0.0),
                "events": events}

    static = run_trace(peak, False)
    record("serve_fleet_static", static["ok"] / max(1, n_req), "frac",
           f"peak-sized static fleet ({peak} replicas): "
           f"{static['ok']}/{n_req} ok, {static['dropped']} dropped; "
           f"ttft p95 {static['ttft_p95_ms']:.0f} ms vs SLO "
           f"{ttft_slo * 1e3:.0f} ms; "
           f"{static['replica_seconds']:.1f} replica-seconds over "
           f"{static['span']:.1f} s — the replica-hours denominator for "
           f"serve_fleet",
           extra={"replica_seconds": round(static["replica_seconds"], 2)})
    if not elastic:
        log("MARLIN_BENCH_FLEET=0: static control leg only")
        return
    el = run_trace(1, True)
    saved = ((static["replica_seconds"] - el["replica_seconds"])
             / static["replica_seconds"]) if static["replica_seconds"] \
        else 0.0
    kinds = collections.Counter(r["action"] for r in el["events"])
    within = el["ttft_steady_p95_ms"] <= ttft_slo * 1e3
    record("serve_fleet", saved, "frac saved",
           f"elastic fleet 1..{peak} replicas: {el['ok']}/{n_req} ok, "
           f"{el['dropped']} dropped; converged ttft p95 "
           f"{el['ttft_steady_p95_ms']:.0f} ms vs SLO "
           f"{ttft_slo * 1e3:.0f} ms "
           f"({'within' if within else 'OVER'}; full-trace "
           f"{el['ttft_p95_ms']:.0f} ms incl. reaction transient); "
           f"{len(el['events'])} scale events ({dict(kinds)}); "
           f"{el['replica_seconds']:.1f} replica-seconds vs static "
           f"{static['replica_seconds']:.1f} "
           f"(replica-hours-saved {max(0.0, saved):.3f})",
           extra={"dropped": el["dropped"],
                  "scale_events": len(el["events"]),
                  "ttft_p95_ms": round(el["ttft_p95_ms"], 1),
                  "ttft_steady_p95_ms": round(el["ttft_steady_p95_ms"], 1),
                  "replica_seconds": round(el["replica_seconds"], 2)})


def config_svd(m=1_000_000, n=512, k=8):
    """Top-k SVD of a tall-skinny matrix via the distributed Gramian +
    matrix-free Lanczos path (the reference's dist-eigs ARPACK mode,
    DenseVecMatrix.scala:1531-1652) — on-chip evidence for the eigensolver."""
    import jax.numpy as jnp

    import marlin_tpu as mt

    mesh = mt.create_mesh()
    a = mt.DenseVecMatrix.random(0, m, n, mesh=mesh)
    float(jnp.sum(a.data))
    svd = a.compute_svd(k, mode="dist-eigs", compute_u=False)  # compile
    t0 = time.perf_counter()
    svd = a.compute_svd(k, mode="dist-eigs", compute_u=False)
    s = np.asarray(svd.s)  # SVDResult.s is host-side — fetch ends the timing
    dt = time.perf_counter() - t0
    assert s.shape[0] == k and np.all(np.diff(s) <= 0), "singular values not sorted"
    record(f"svd_{m}x{n}_top{k}", dt, "s", f"dist-eigs Gramian+Lanczos, "
           f"sigma_max {s[0]:.1f}")


def config_als(users=1_000_000, items=100_000, rank=32, nnz=10_000_000,
               iters=3):
    """Blocked ALS at MovieLens-10M-ish scale on one chip: wall clock per
    sweep plus the RMSE trajectory (reference workload: examples/ALS.scala →
    ALSHelp.ALSRun)."""
    import marlin_tpu as mt

    mesh = mt.create_mesh()
    rng = np.random.default_rng(0)
    ui = rng.integers(0, users, nnz).astype(np.int32)
    ii = rng.integers(0, items, nnz).astype(np.int32)
    u_t = rng.standard_normal((users, 8)).astype(np.float32) / 8.0
    v_t = rng.standard_normal((items, 8)).astype(np.float32)
    vals = np.einsum("nk,nk->n", u_t[ui], v_t[ii]) + \
        0.1 * rng.standard_normal(nnz).astype(np.float32)
    coo = mt.CoordinateMatrix(ui, ii, vals, shape=(users, items), mesh=mesh)
    model = coo.als(rank=rank, iterations=1, lam=0.05)  # compile + H2D
    mt.evaluate(model.user_features, model.product_features)
    t0 = time.perf_counter()
    model = coo.als(rank=rank, iterations=iters, lam=0.05)
    # data-dependent fetch inside the timed region: async dispatch otherwise
    # means the clock reads dispatch latency, not compute (profiling.evaluate)
    mt.evaluate(model.user_features, model.product_features)
    dt = time.perf_counter() - t0
    rmse = model.rmse(coo)
    record(f"als_{users}x{items}_r{rank}_{nnz}nnz", dt / iters, "s/sweep",
           f"{iters} sweeps in {dt:.1f} s, rmse {rmse:.3f}")


def config_accuracy(n=20000, rows=128):
    """On-TPU numerics evidence (VERDICT r1 #9): rel-err of one row block of
    the north-star multiply against a *host* f64 oracle (independent hardware,
    independent arithmetic; D2H bounded to 3 row blocks), plus the
    default-vs-high precision delta proving the ``precision`` kwarg reaches
    the MXU (bf16 passes vs f32 — indistinguishable on the CPU mesh, where
    tests/test_strategy_equivalence.py documents the blind spot)."""
    import jax
    import jax.numpy as jnp

    import marlin_tpu as mt

    mesh = mt.create_mesh()
    a = mt.DenseVecMatrix.random(0, n, n, mesh=mesh)
    b = mt.DenseVecMatrix.random(1, n, n, mesh=mesh)
    # "default" must be requested explicitly: the library config default is
    # "highest" (config.matmul_precision), so a bare multiply runs the full-
    # f32 path — comparing that against "high" proves nothing about bf16
    c_hi = a.multiply(b, precision="high")
    c_def = a.multiply(b, precision="default")
    hi_rows = np.asarray(jax.device_get(c_hi.data[:rows]), np.float64)
    def_rows = np.asarray(jax.device_get(c_def.data[:rows]), np.float64)
    dev_a_rows = np.asarray(jax.device_get(a.data[:rows]))

    # regenerate the operands on the host CPU backend — threefry is
    # counter-based and backend-deterministic, so this is the same data
    # without a 3.2 GB D2H; verify that claim bitwise on the fetched rows
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        a_cpu = np.asarray(mt.random.random_array(0, (n, n)))
        b_cpu = np.asarray(mt.random.random_array(1, (n, n)))
    assert np.array_equal(a_cpu[:rows], dev_a_rows), \
        "host regeneration diverged from device operand — oracle invalid"
    oracle = a_cpu[:rows].astype(np.float64) @ b_cpu.astype(np.float64)
    scale = np.abs(oracle).max()
    err_hi = float(np.abs(hi_rows - oracle).max() / scale)
    err_def = float(np.abs(def_rows - oracle).max() / scale)
    ratio = err_def / max(err_hi, 1e-30)
    plumbed = "kwarg reaches the MXU" if ratio > 3 else (
        "WARNING: default≈high — expected only off-TPU, where both paths "
        "compute f32")
    record(f"acc_{n}_rowblock_f64_oracle", err_hi, "rel err",
           f"precision=high {err_hi:.2e} vs host f64; "
           f"default(bf16)={err_def:.2e}, ratio {ratio:.0f}x — {plumbed}")


def main():
    from marlin_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    which = sys.argv[1:] or ["1", "2", "3", "4", "5"]
    steps = {
        "1": config1,
        # 100 reps so the one sync round-trip amortizes out of the
        # per-multiply figure
        "2": lambda: _dense_config(4000, 100, "2_dense_4000"),
        "3": lambda: _dense_config(20000, 5, "3_dense_20000"),
        # the bf16-storage speed story (accuracy story lives in `acc`):
        # same 20000^2 multiply with bf16 MXU operands
        "bf16": lambda: _dense_config(20000, 10, "3_dense_20000_bf16",
                                      precision="default"),
        "4": config4,
        # the file-fed data-plane A/B alone (it also runs at the tail of
        # config 4): re-measure the text-vs-chunkstore legs without the
        # 8 GB synthetic-generation legs in front
        "4file": _config4_file_legs,
        "5": config5,
        "lu": config_lu,
        "chol": config_cholesky,
        "attn": config_attention,
        "pr": config_pagerank,
        "acc": config_accuracy,
        "als": config_als,
        "bsr": config_bsr,
        "svd": config_svd,
        "nn": config_nn,
        "lct": config_lct,
        "lct_long": config_lct_long,
        "attn_long": config_attn_long,
        "decode": config_decode,
        "moe": config_moe,
        "serve": config_serve,
        "serve_als": config_serve_als,
        "serve_slo": config_serve_slo,
        "fleet": config_fleet,
    }
    for k in which:
        log(f"=== config {k}")
        try:
            steps[k]()
        except Exception as e:  # keep the sweep going
            log(f"config {k} FAILED: {type(e).__name__}: {e}")
            record(f"{k}_FAILED", 0.0, "error", str(e)[:200])

    # merge with prior runs so partial sweeps don't clobber the table
    merged = {}
    if os.path.exists(RESULTS_PATH):
        try:
            merged = {r["config"]: r for r in json.load(open(RESULTS_PATH))}
        except Exception:
            merged = {}
    for r in RESULTS:
        merged[r["config"]] = r
    ordered = [merged[k] for k in sorted(merged)]
    with open(RESULTS_PATH, "w") as f:
        json.dump(ordered, f, indent=1)
    with open("BENCHMARKS.md", "w") as f:
        f.write("# Benchmarks\n\n")
        f.write("Configs from BASELINE.md; generated by `python bench_all.py`\n")
        f.write("from BENCH_ALL.json. Each row's Measured column says when it\n")
        f.write("was taken; rows older than the code they name are stale.\n\n")
        f.write("| Config | Value | Unit | Measured | Detail |\n"
                "|---|---|---|---|---|\n")
        for r in ordered:
            # entries from before the provenance stamp are round-2-or-earlier
            # by definition (the stamp shipped in round 4)
            when = r.get("measured", "≤r2 (pre-provenance; stale)")
            f.write(f"| {r['config']} | {r['value']} | {r['unit']} | {when} "
                    f"| {r['detail']} |\n")


if __name__ == "__main__":
    main()
