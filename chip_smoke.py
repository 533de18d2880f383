#!/usr/bin/env python
"""Does the system still start on the chip?  ``python chip_smoke.py``

Drives the library's main paths once, through the entry points a user calls,
in ONE process on ONE TPU chip, at the sizes the repo's own records name
(BASELINE.md configs 1, 3, 4 and the ``r5`` rows of PERF.md's table "Chip
rows older than PR 1"):

- matrix: the file-loaded 100x100 multiply (genmat + the native data plane,
  built from source), the 20000^2 adaptive multiply against a host float64
  row-block oracle, ``lu_decompose(mode="dist")`` at 8192^2, the
  host-streamed Gramian at 1,000,000 x 512;
- LM train -> serve: ``TransformerLM(vocab=4096, d_model=512, heads=8,
  layers=4)`` trained on ``synthetic_stream``, then served by a supervised
  ``ServeEngine`` with its defaults (paged pool, chunked prefill, the Pallas
  paged-decode kernel), tokens compared with ``lm_generate``;
- long context: a 32768-token ``lm.train`` through flash ring attention, and
  ``ring_attention`` flash against xla at 32768x128.

Every phase prints one JSON object and raises on a failed check; nothing is
caught, so any failure ends the run non-zero. The last line of a passing run
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero and prints no such line.

``--chips 4`` runs ONLY the mesh phases (the 20000^2 ``rmm`` multiply with the
contraction axis split over a 2x2 mesh, the adaptive multiply on the same
mesh, ring attention and one train step over a 4-device ring) and needs four
chips. ``--size tiny`` shrinks every size for a CPU rehearsal of the control
flow — on a CPU the run still ends non-zero, because the device is not a TPU.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SIZES = {
    "full": dict(
        dense_n=20000, oracle_rows=128, lu_n=8192,
        gram_rows=1_000_000, gram_cols=512, gram_chunk=1 << 17,
        lm=dict(vocab=4096, d_model=512, heads=8, layers=4),
        train_seq=2048, train_steps=40,
        lct=dict(vocab=512, d_model=256, heads=2, layers=2),
        lct_seq=32768, attn_seq=32768, attn_d=128),
    "tiny": dict(
        dense_n=384, oracle_rows=32, lu_n=256,
        gram_rows=8192, gram_cols=64, gram_chunk=2048,
        lm=dict(vocab=256, d_model=64, heads=4, layers=2),
        train_seq=1024, train_steps=60,
        lct=dict(vocab=64, d_model=64, heads=2, layers=1),
        lct_seq=1024, attn_seq=1024, attn_d=128),
}


def emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes():
    """The process's device high-water, where the backend reports one."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def twice(fn):
    """(result, seconds of the first call — compile included —, seconds of a
    second, warm call). Single readings: liveness evidence, not a benchmark."""
    t0 = time.perf_counter()
    fn()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn()
    return out, round(first_s, 3), round(time.perf_counter() - t0, 5)


# ------------------------------------------------------------------- matrix


def phase_file_multiply():
    """BASELINE config 1: genmat -> text files -> native loader -> multiply."""
    import marlin_tpu as mt
    from marlin_tpu import native

    tools = os.path.join(HERE, "tools")
    subprocess.run(["make", "-s", "-C", tools], check=True)
    with tempfile.TemporaryDirectory() as d:
        paths = []
        for name, seed in (("a", 1), ("b", 2)):
            paths.append(os.path.join(d, f"{name}.txt"))
            with open(paths[-1], "w") as f:
                subprocess.run([os.path.join(tools, "genmat"), "100", "100",
                                str(seed)], stdout=f, check=True)
        mesh = mt.create_mesh()
        a = mt.load_matrix_file(paths[0], mesh)
        b = mt.load_matrix_file(paths[1], mesh)
        c, first_s, run_s = twice(lambda: mt.evaluate(a.multiply(b)))
        # the pure-Python data plane is a fallback: it must not be what ran
        check(native.build_error() is None,
              f"native build failed: {native.build_error()}")
        check(native.available() and native.chunkstore_available(),
              "native libraries did not load")
    ref = a.to_numpy().astype(np.float64) @ b.to_numpy().astype(np.float64)
    err = float(np.abs(c.to_numpy() - ref).max() / np.abs(ref).max())
    check(a.shape == (100, 100) and err < 1e-5, f"file multiply err {err}")
    emit("matrix_file_100", sizes=[100, 100, 100], native="built",
         first_s=first_s, run_s=run_s, rel_err=err,
         peak_bytes_in_use=peak_bytes())


def phase_multiply(sz, mesh, **kw):
    """BASELINE config 3: random 20000^2 x 20000^2 through
    ``DenseVecMatrix.multiply``, a row block of the product against a host
    float64 product of the SAME operands (fetched from the device). The
    product must live on every device of ``mesh``: code that has only met
    one chip may have put everything on the first."""
    import marlin_tpu as mt

    n, rows = sz["dense_n"], sz["oracle_rows"]
    a = mt.DenseVecMatrix.random(0, n, n, mesh=mesh)
    b = mt.DenseVecMatrix.random(1, n, n, mesh=mesh)
    c, first_s, run_s = twice(
        lambda: mt.evaluate(a.multiply(b, precision="high", **kw)))
    check(c.shape == (n, n), f"product shape {c.shape}")
    oracle = np.asarray(a.data[:rows], np.float64)[:, :n] \
        @ np.asarray(b.data, np.float64)[:n, :n]
    got = np.asarray(c.data[:rows], np.float64)[:, :n]
    err = float(np.abs(got - oracle).max() / np.abs(oracle).max())
    check(np.isfinite(err) and err < 1e-4, f"multiply rel err {err}")
    shards = c.data.addressable_shards
    shard_devs = sorted({str(s.device) for s in shards})
    check(len(c.data.sharding.device_set) == len(shard_devs) == mesh.size,
          f"product on {shard_devs}, mesh has {mesh.size} devices")
    emit("matrix_multiply", sizes=[n, n, n], precision="high",
         mesh=dict(mesh.shape), strategy=kw.get("strategy", "auto"),
         split=kw.get("split"), oracle_rows=rows, rel_err=err,
         shard_devices=shard_devs, shard_shape=list(shards[0].data.shape),
         first_s=first_s, run_s=run_s, peak_bytes_in_use=peak_bytes())


def phase_lu(sz):
    """``lu_decompose(mode="dist")``: residual |A[p] - L U| / |A|."""
    import jax.numpy as jnp

    import marlin_tpu as mt

    n = sz["lu_n"]
    mesh = mt.create_mesh()
    a = mt.BlockMatrix.random(0, n, n, mesh=mesh).add(
        mt.BlockMatrix.from_array(float(n) * np.eye(n, dtype=np.float32),
                                  mesh))
    (l, u, p), first_s, run_s = twice(
        lambda: mt.evaluate(*a.lu_decompose(mode="dist")))
    al = a.logical()
    lu = jnp.dot(l.logical(), u.logical(), precision="highest")
    resid = float(jnp.linalg.norm(al[p] - lu) / jnp.linalg.norm(al))
    check(sorted(np.asarray(p).tolist()) == list(range(n)),
          "LU permutation is not a permutation")
    check(np.isfinite(resid) and resid < 1e-4, f"LU residual {resid}")
    emit("matrix_lu_dist", sizes=[n, n], residual=resid, first_s=first_s,
         run_s=run_s, peak_bytes_in_use=peak_bytes())


def phase_streamed_gramian(sz):
    """BASELINE config 4's path (cut from 10^7 rows for time): A^T A with A
    resident on the HOST and streamed through the device in row chunks by
    the async prefetcher, against the blockwise NumPy float64 result."""
    import marlin_tpu as mt
    from marlin_tpu.config import get_config
    from marlin_tpu.utils.profiling import StageTimes

    rows, cols, chunk = sz["gram_rows"], sz["gram_cols"], sz["gram_chunk"]
    check(get_config().prefetch_enabled, "prefetch is off by default?")
    host = np.random.default_rng(0).random((rows, cols), np.float32)
    ooc = mt.OutOfCoreMatrix(host, chunk_rows=chunk)
    mt.streamed_gramian(iter([host[:chunk], host[: rows % chunk or chunk]]))
    stats = StageTimes()
    t0 = time.perf_counter()
    g = ooc.gramian(stats=stats)
    run_s = time.perf_counter() - t0
    ref = np.zeros((cols, cols), np.float64)
    for s in range(0, rows, chunk):
        x = host[s:s + chunk].astype(np.float64)
        ref += x.T @ x
    err = float(np.abs(g - ref).max() / np.abs(ref).max())
    check(g.shape == (cols, cols) and err < 1e-4, f"gramian rel err {err}")
    emit("matrix_streamed_gramian", rows=rows, cols=cols, chunk_rows=chunk,
         h2d_bytes=host.nbytes, rel_err=err, run_s=round(run_s, 3),
         stages=stats.summary(), peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------- LM phases

PERIOD, STEP = 16, 7


def step_programs():
    """Programs ``lm_train_step`` has compiled so far. It compiles again when
    fed its own outputs (committed, mesh-sharded) in place of fresh
    ``init_params``/``adam.init`` arrays, so a train call's seconds hold two
    or three compiles, not one: read them next to this count."""
    from marlin_tpu.models.transformer import lm_train_step

    return lm_train_step._cache_size()


def phase_lm_train(sz):
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.models.transformer import synthetic_stream

    lm = TransformerLM(learning_rate=1e-3, seed=0, **sz["lm"])
    stream = synthetic_stream(sz["train_seq"], vocab=lm.vocab, period=PERIOD,
                              step=STEP, noise=0.05)
    n0, t0 = step_programs(), time.perf_counter()
    params, losses = lm.train(stream, steps=sz["train_steps"])
    train_s = time.perf_counter() - t0
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < 0.5 * losses[0], f"loss did not fall: {losses}")
    emit("lm_train", model=sz["lm"], tokens=sz["train_seq"],
         steps=len(losses), loss_first=losses[0], loss_last=losses[-1],
         train_s=round(train_s, 3), step_programs=step_programs() - n0,
         peak_bytes_in_use=peak_bytes())
    return lm, params


def serve_requests(lm):
    """A dozen ragged greedy requests across both default buckets (every
    prompt twice), then four repeats for the second wave: by then the first
    wave's prompt pages are in the prefix cache, so those rows read pages
    another request wrote."""
    from marlin_tpu.models.transformer import synthetic_stream
    from marlin_tpu.serving import Request

    clean = synthetic_stream(512, vocab=lm.vocab, period=PERIOD, step=STEP,
                             noise=0.0)
    shapes = [(5, 16), (23, 32), (61, 24), (90, 48), (177, 64), (250, 40)]
    def req(n, steps):
        return Request(prompt=clean[:n], steps=steps, max_attempts=3)

    return ([req(n, steps) for n, steps in shapes for _ in range(2)],
            [req(n, steps) for n, steps in shapes[1:5]])


def explain_mismatch(lm, params, req, got, want):
    """The reference's top-2 logit margin at the first differing step, and
    the matmul error it is held against (two bf16 roundings of the logit
    scale: on the chip the decode matmuls run bf16 passes and the three
    formulations are not bit-identical)."""
    import jax

    from marlin_tpu.models import transformer_forward

    step = int(np.argmax(got != want))
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(transformer_forward(
            params, want[:step], heads=lm.heads)[-1], np.float64)
    top2 = np.sort(logits)[-2:]
    return {"rid": req.rid, "step": step, "got": int(got[step]),
            "want": int(want[step]), "margin": float(top2[1] - top2[0]),
            "tol": float(2.0 ** -7 * np.abs(logits).max())}


def phase_lm_serve(lm, params, on_tpu):
    import jax

    from marlin_tpu.models import lm_generate
    from marlin_tpu.obs import memledger
    from marlin_tpu.serving import STATUS_OK, ServeEngine, Supervisor

    eng = ServeEngine(params, lm.heads)  # every knob at its default
    want_kernel = "pallas" if on_tpu else "gather"
    check(eng._decode_kernel == want_kernel,
          f"engine resolved kernel={eng._decode_kernel}")
    sup = Supervisor(eng)
    reqs, repeats = serve_requests(lm)
    handles = [None] * len(reqs)
    try:
        t0 = time.perf_counter()
        eng.warmup()
        warm_s = time.perf_counter() - t0

        def submit(idx):
            for i in idx:
                handles[i] = eng.submit(reqs[i])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=submit, args=(range(k, len(reqs), 2),))
                   for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            check(not t.is_alive(), "submitter thread hung")
        results = [h.result(timeout=600) for h in handles]
        results += [h.result(timeout=600)
                    for h in [eng.submit(r) for r in repeats]]
        serve_s = time.perf_counter() - t0
        reqs = reqs + repeats
        snap = eng.metrics.snapshot()
        mid = memledger.reconcile()
        planner_pool_bytes = eng._num_pages * eng._page_bytes
        slab_device_bytes = sum(x.on_device_size_in_bytes()
                                for x in jax.tree.leaves(eng._kvpool.pages))
    finally:
        eng.drain()
        sup.close()
    check(sup.restart_count == 0 and not sup.breaker_open,
          f"worker restarted {sup.restart_count}x")
    check(snap["retries"] == 0 and snap["errors"] == 0, f"retries: {snap}")
    check(snap["prefix_hits"] >= len(repeats), f"prefix cache cold: {snap}")
    mismatches = []
    for req, res in zip(reqs, results):
        check(res.status == STATUS_OK, f"rid {req.rid}: {res.status} "
                                       f"{res.reason}")
        n = len(req.prompt)
        got = np.asarray(res.tokens)
        check(got.shape == (n + req.steps,), f"rid {req.rid}: {got.shape}")
        want = np.asarray(lm_generate(
            params, req.prompt, jax.random.key(0), heads=lm.heads,
            max_len=n + req.steps, steps=req.steps))
        if not np.array_equal(got, want):
            mismatches.append(explain_mismatch(lm, params, req, got, want))
    for m in mismatches:
        emit("lm_serve_mismatch", **m)
    check(all(m["margin"] <= m["tol"] for m in mismatches),
          "served tokens differ from lm_generate beyond the matmul error")
    emit("lm_serve", kernel=eng._decode_kernel, buckets=list(eng.buckets),
         max_batch=eng.max_batch, page_len=eng._page_len,
         num_pages=eng._num_pages, requests=len(reqs),
         new_tokens=snap["new_tokens"], decode_steps=snap["steps"],
         prefix_hits=snap["prefix_hits"], restarts=sup.restart_count,
         retries=snap["retries"], token_mismatches=len(mismatches),
         p50_step_s=snap["p50_step_s"],
         p50_ttft_s=snap["p50_ttft_s"], busy_s=snap["busy_s"],
         warmup_s=round(warm_s, 3), serve_s=round(serve_s, 3),
         peak_bytes_in_use=peak_bytes())
    # the ledger's three views: what it registered, what the device reports
    # live, what the planner charged — mid-serve, and after the engine left
    audit = memledger.get_ledger().audit()
    closed = memledger.reconcile()
    emit("memory_ledger", planner_pool_bytes=planner_pool_bytes,
         slab_device_bytes=slab_device_bytes,
         serving={k: mid[k] for k in ("registered_bytes", "components",
                                      "live_bytes", "unattributed_bytes",
                                      "unattributed_frac")},
         closed={k: closed[k] for k in ("registered_bytes", "live_bytes",
                                        "unattributed_bytes")},
         audit_ok=audit["ok"], audit_errors=audit["errors"])
    check(audit["ok"], f"ledger audit: {audit['errors']}")
    check(mid["components"].get("kvpool", 0) > 0, "kvpool never registered")
    check(closed["components"].get("kvpool", 0) == 0,
          "engine left its slab in the ledger")
    check(not on_tpu or mid["live_bytes"], "no memory_stats on a TPU?")


def phase_long_context(sz, mesh=None, steps=3):
    """``lct``: the Pallas flash forward + two-pass backward under lm.train."""
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.models.transformer import synthetic_stream

    lm = TransformerLM(attn="ring", **sz["lct"])
    toks = synthetic_stream(sz["lct_seq"], vocab=lm.vocab, period=PERIOD,
                            step=STEP, noise=0.1)
    n0, t0 = step_programs(), time.perf_counter()
    params, losses = lm.train(toks, steps=1, mesh=mesh)
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    if steps:
        params, more = lm.train(toks, steps=steps, mesh=mesh, params=params)
        losses = losses + more
    rest_s = time.perf_counter() - t0
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(not steps or losses[-1] < losses[0], f"loss did not fall: {losses}")
    emit("long_context_train", model=sz["lct"], tokens=sz["lct_seq"],
         ring=1 if mesh is None else mesh.shape["rows"], losses=losses,
         first_s=round(first_s, 3), rest_s=round(rest_s, 3),
         step_programs=step_programs() - n0, peak_bytes_in_use=peak_bytes())


def phase_ring_attention(sz, mesh=None):
    """``ring_attention`` flash (Pallas) against xla, causal; and the flash
    backward's dq/dk/dv against the dense reference's at 1024 tokens."""
    import jax
    import jax.numpy as jnp

    import marlin_tpu as mt
    from marlin_tpu.parallel.ring_attention import attention_reference

    seq, d = sz["attn_seq"], sz["attn_d"]
    mesh = mesh or mt.create_mesh()
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((seq, d)).astype(np.float32))
               for _ in range(3))
    outs, secs = {}, {}
    for backend in ("flash", "xla"):
        t0 = time.perf_counter()
        outs[backend] = mt.evaluate(mt.ring_attention(
            q, k, v, mesh, causal=True, backend=backend))
        secs[backend] = round(time.perf_counter() - t0, 3)
    err = float(jnp.abs(outs["flash"] - outs["xla"]).max()
                / jnp.abs(outs["xla"]).max())
    check(outs["flash"].shape == (seq, d) and err < 1e-3,
          f"flash vs xla rel err {err}")
    qs, ks, vs = q[:1024], k[:1024], v[:1024]
    got = jax.jit(jax.grad(lambda *x: jnp.sum(mt.ring_attention(
        *x, mesh, causal=True, backend="flash")), argnums=(0, 1, 2)))(
            qs, ks, vs)
    want = jax.vjp(lambda *x: attention_reference(*x, causal=True),
                   qs, ks, vs)[1](jnp.ones((1024, d), jnp.float32))
    bwd = {n: float(jnp.abs(g - w).max() / jnp.abs(w).max())
           for n, g, w in zip(("dq", "dk", "dv"), got, want)}
    check(all(e < 1e-3 for e in bwd.values()), f"flash bwd rel err {bwd}")
    emit("ring_attention", sizes=[seq, d], causal=True,
         ring=mesh.shape["rows"], flash_vs_xla_rel_err=err,
         flash_bwd_rel_err_1024=bwd, first_s=secs,
         peak_bytes_in_use=peak_bytes())


# ------------------------------------------------------------- --chips 4


def run_four_chips(sz):
    import jax

    import marlin_tpu as mt

    check(len(jax.devices()) == 4, f"--chips 4 found {len(jax.devices())}")
    mesh = mt.create_mesh((2, 2))
    phase_multiply(sz, mesh, strategy="rmm", split=(2, 2, 1))
    phase_multiply(sz, mesh)
    ring = mt.create_mesh((4, 1))
    phase_ring_attention(sz, ring)
    phase_long_context(sz, ring, steps=0)


def run_one_chip(sz, on_tpu):
    import marlin_tpu as mt

    phase_file_multiply()
    phase_multiply(sz, mt.create_mesh())
    phase_lu(sz)
    phase_streamed_gramian(sz)
    lm, params = phase_lm_train(sz)
    phase_lm_serve(lm, params, on_tpu)
    phase_long_context(sz)
    phase_ring_attention(sz)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--size", choices=tuple(SIZES), default="full")
    args = ap.parse_args(argv)

    from marlin_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    cache = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and args.size == "full":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {device} "
                 "(--size tiny rehearses the phases on a CPU)")
    emit("start", device=device, chips=args.chips, size=args.size,
         compile_cache=cache_dir)
    t0 = time.perf_counter()
    sz = SIZES[args.size]
    if args.chips == 4:
        run_four_chips(sz)
    else:
        run_one_chip(sz, on_tpu)
    emit("done", seconds=round(time.perf_counter() - t0, 1),
         compile_cache=cache_dir, cache_hits=cache["hits"],
         cache_misses=cache["misses"], peak_bytes_in_use=peak_bytes())
    if not on_tpu:
        sys.exit(f"every phase passed, but on {device}: not a TPU, not ok")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
