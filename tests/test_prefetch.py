"""Async host→device prefetch pipeline (parallel/prefetch.py).

Covers the pipeline's contract (ordering, backpressure, exception
propagation, clean shutdown), its chaos hooks (prefetch.produce fault point),
and the property the whole design rests on: streamed results are bit-for-bit
identical with prefetch on and off.
"""

import struct
import threading
import time

import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu.parallel.prefetch import ChunkPrefetcher
from marlin_tpu.utils import faults
from marlin_tpu.utils.profiling import StageTimes


def _chunks(n=8, rows=16, cols=4, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((rows, cols)).astype(np.float32)
            for _ in range(n)]


def _alive_workers():
    return [t for t in threading.enumerate()
            if t.name.startswith("marlin-prefetch") and t.is_alive()]


# ------------------------------------------------------------------ contract
def test_ordering_single_worker():
    cs = _chunks(10)
    got = list(ChunkPrefetcher(iter(cs), workers=1, depth=2))
    assert len(got) == len(cs)
    for g, c in zip(got, cs):
        np.testing.assert_array_equal(np.asarray(g), c)


def test_ordering_many_workers():
    """Out-of-order completion (4 workers racing) must still yield source
    order — the reorder buffer, not scheduling luck."""
    cs = _chunks(24)
    got = list(ChunkPrefetcher(iter(cs), workers=4, depth=6))
    assert len(got) == len(cs)
    for g, c in zip(got, cs):
        np.testing.assert_array_equal(np.asarray(g), c)


def test_transform_runs_on_producer():
    cs = _chunks(6)
    tids = set()

    def transform(c):
        tids.add(threading.get_ident())
        return c * 2.0

    got = list(ChunkPrefetcher(iter(cs), transform, workers=2))
    for g, c in zip(got, cs):
        np.testing.assert_array_equal(np.asarray(g), c * 2.0)
    assert threading.get_ident() not in tids  # off the consumer's thread


def test_backpressure_bounds_inflight_chunks():
    """With depth=d and an idle consumer, at most d chunks are ever read."""
    produced = []

    def source():
        for c in _chunks(20):
            produced.append(len(produced))
            yield c

    pf = ChunkPrefetcher(source(), depth=3, workers=2)
    try:
        deadline = time.monotonic() + 2.0
        while len(produced) < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.3)  # give eager workers every chance to overshoot
        assert len(produced) == 3
        # consuming one admits exactly one more read
        next(pf)
        deadline = time.monotonic() + 2.0
        while len(produced) < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)
        assert len(produced) == 4
    finally:
        pf.close()


def test_hbm_budget_admission_is_stream_ordered():
    """Regression: with several workers racing a budget smaller than one
    chunk, chunk i+1's worker must not claim the budget ahead of chunk i's —
    the consumer needs i first, and i's worker would wait forever on a budget
    held by a chunk nobody can consume yet. Ordered admission makes this
    terminate; many chunks × many reps made the inversion near-certain under
    the old first-come admission."""
    for rep in range(5):
        cs = _chunks(40, rows=8, seed=rep)
        got = list(ChunkPrefetcher(iter(cs), depth=6, workers=4,
                                   hbm_budget_bytes=1))
        assert len(got) == len(cs)
        for g, c in zip(got, cs):
            np.testing.assert_array_equal(np.asarray(g), c)
    assert not _alive_workers()


def test_hbm_budget_lets_single_chunk_through():
    """A budget smaller than one chunk must not deadlock: a lone chunk always
    proceeds, the stream just serializes."""
    cs = _chunks(5)
    got = list(ChunkPrefetcher(iter(cs), depth=3, workers=2,
                               hbm_budget_bytes=1))
    assert len(got) == len(cs)
    for g, c in zip(got, cs):
        np.testing.assert_array_equal(np.asarray(g), c)


def test_source_exception_propagates_in_position():
    boom = ValueError("disk on fire")

    def source():
        for i, c in enumerate(_chunks(10)):
            if i == 3:
                raise boom
            yield c

    pf = ChunkPrefetcher(source(), workers=2, depth=4)
    got = [next(pf), next(pf), next(pf)]  # 0..2 still delivered
    assert len(got) == 3
    with pytest.raises(ValueError, match="disk on fire"):
        next(pf)
    assert not _alive_workers()  # error path joins the workers too


def test_transform_exception_propagates():
    def transform(c):
        if float(c[0, 0]) == 2.0:
            raise RuntimeError("bad chunk")
        return c

    cs = [np.full((4, 4), float(i), np.float32) for i in range(5)]
    pf = ChunkPrefetcher(iter(cs), transform, workers=2, depth=4)
    assert float(np.asarray(next(pf))[0, 0]) == 0.0
    assert float(np.asarray(next(pf))[0, 0]) == 1.0
    with pytest.raises(RuntimeError, match="bad chunk"):
        next(pf)
    assert not _alive_workers()


def test_transform_failure_under_tight_budget_refunds():
    """Regression: a post-admission failure must refund the HBM budget and
    advance the admission cursor, or successors stall against a phantom
    occupant. With budget=1 every chunk needs a full refund cycle."""
    def transform(c):
        if float(c[0, 0]) == 1.0:
            raise RuntimeError("bad chunk")
        return c

    cs = [np.full((8, 8), float(i), np.float32) for i in range(6)]
    pf = ChunkPrefetcher(iter(cs), transform, workers=3, depth=4,
                         hbm_budget_bytes=1)
    assert float(np.asarray(next(pf))[0, 0]) == 0.0
    with pytest.raises(RuntimeError, match="bad chunk"):
        next(pf)
    assert not _alive_workers()


def test_close_midstream_joins_threads():
    pf = ChunkPrefetcher(iter(_chunks(50)), workers=3, depth=2)
    next(pf)
    pf.close()
    assert not _alive_workers()
    with pytest.raises(StopIteration):
        next(pf)  # closed pipeline is exhausted, not wedged
    pf.close()  # idempotent


def test_context_manager_abandon():
    with ChunkPrefetcher(iter(_chunks(30)), workers=2) as pf:
        next(pf)
    assert not _alive_workers()


def test_exhaustion_closes_automatically():
    pf = ChunkPrefetcher(iter(_chunks(4)))
    assert len(list(pf)) == 4
    assert not _alive_workers()


def test_bad_knobs_rejected():
    with pytest.raises(ValueError):
        ChunkPrefetcher(iter([]), depth=0)
    with pytest.raises(ValueError):
        ChunkPrefetcher(iter([]), workers=0)


# ------------------------------------------------------------------- chaos
def test_chaos_delayed_producer_still_correct():
    cs = _chunks(6)
    with faults.injected("prefetch.produce",
                         faults.DelayFault(0.05, match="chunk-2")):
        got = list(ChunkPrefetcher(iter(cs), workers=2, depth=3))
    for g, c in zip(got, cs):
        np.testing.assert_array_equal(np.asarray(g), c)


def test_chaos_raising_producer_propagates():
    cs = _chunks(8)
    with faults.injected("prefetch.produce",
                         faults.RaiseFault(match="chunk-3")) as f:
        pf = ChunkPrefetcher(iter(cs), workers=2, depth=4)
        got = [next(pf), next(pf), next(pf)]
        with pytest.raises(faults.FaultInjected):
            next(pf)
    assert f.fired == 1
    assert len(got) == 3
    assert not _alive_workers()


def test_chaos_through_streamed_gramian():
    """The fault surfaces through the public streamed op, and the op's
    worker threads still shut down."""
    a = np.random.default_rng(3).standard_normal((256, 8)).astype(np.float32)
    with faults.injected("prefetch.produce", faults.RaiseFault(match="chunk-1")):
        with pytest.raises(faults.FaultInjected):
            mt.streamed_gramian(a, chunk_rows=64, prefetch=True)
    assert not _alive_workers()


# -------------------------------------------------------------- equivalence
def test_streamed_matmul_equivalence_on_off(compile_count):
    """Prefetch on/off is bit-for-bit equivalent, and streaming traffic is
    compile-bounded: once both paths have run, further streamed multiplies
    add ZERO XLA compiles — the same compile-bound guard the serving suite
    uses (tests/conftest.py compile_count)."""
    rng = np.random.default_rng(7)
    a = rng.standard_normal((640, 24)).astype(np.float32)
    b = rng.standard_normal((24, 8)).astype(np.float32)
    off = mt.streamed_matmul(a, b, chunk_rows=100, prefetch=False)
    on = mt.streamed_matmul(a, b, chunk_rows=100, prefetch=True)
    np.testing.assert_array_equal(on, off)  # bit-for-bit, not allclose
    with compile_count() as c:
        again = mt.streamed_matmul(a, b, chunk_rows=100, prefetch=True)
    np.testing.assert_array_equal(again, off)
    assert c.count == 0, \
        f"a warm streamed multiply recompiled ({c.count} programs)"


def test_streamed_gramian_equivalence_on_off():
    a = np.random.default_rng(8).standard_normal((512, 16)).astype(np.float32)
    on = mt.streamed_gramian(a, chunk_rows=96, prefetch=True)
    off = mt.streamed_gramian(a, chunk_rows=96, prefetch=False)
    np.testing.assert_array_equal(on, off)


def test_equivalence_with_transfer_compression():
    """bf16 transfer compression composes with prefetch: the host-side cast
    moves to producer threads, the math is unchanged."""
    a = np.random.default_rng(9).standard_normal((256, 12)).astype(np.float32)
    on = mt.streamed_gramian(a, chunk_rows=64, transfer_dtype="bfloat16",
                             prefetch=True)
    off = mt.streamed_gramian(a, chunk_rows=64, transfer_dtype="bfloat16",
                              prefetch=False)
    np.testing.assert_array_equal(on, off)


def test_out_of_core_ops_equivalence():
    rng = np.random.default_rng(10)
    big = rng.standard_normal((800, 16)).astype(np.float32)
    b = rng.standard_normal((16, 4)).astype(np.float32)
    ooc = mt.OutOfCoreMatrix(big, chunk_rows=128)
    np.testing.assert_array_equal(ooc.multiply(b, prefetch=True),
                                  ooc.multiply(b, prefetch=False))
    np.testing.assert_array_equal(ooc.gramian(prefetch=True),
                                  ooc.gramian(prefetch=False))
    assert ooc.sum(prefetch=True) == ooc.sum(prefetch=False)


def test_config_flag_controls_default(monkeypatch):
    """prefetch=None follows config.prefetch_enabled; explicit True overrides."""
    constructed = []
    orig = ChunkPrefetcher

    def spy(*args, **kw):
        constructed.append(1)
        return orig(*args, **kw)

    monkeypatch.setattr("marlin_tpu.parallel.streaming.ChunkPrefetcher", spy)
    a = np.ones((64, 4), np.float32)
    with mt.config_context(prefetch_enabled=False):
        mt.streamed_gramian(a, chunk_rows=32)
        assert not constructed
        mt.streamed_gramian(a, chunk_rows=32, prefetch=True)
        assert len(constructed) == 1


def test_empty_stream_still_raises():
    b = np.ones((4, 4), np.float32)
    for prefetch in (True, False):
        with pytest.raises(ValueError, match="empty input stream"):
            mt.streamed_matmul(iter([]), b, prefetch=prefetch)
        with pytest.raises(ValueError, match="empty input stream"):
            mt.streamed_gramian(iter([]), prefetch=prefetch)
    assert not _alive_workers()


# ---------------------------------------------------------- instrumentation
def test_stage_times_recorded():
    a = np.random.default_rng(11).standard_normal((512, 8)).astype(np.float32)
    st = StageTimes()
    mt.streamed_gramian(a, chunk_rows=64, prefetch=True, stats=st)
    for stage in ("produce", "transfer", "stall", "compute", "drain"):
        assert stage in st.seconds, f"missing stage {stage}: {st.summary()}"
    assert st.counts["produce"] == 8  # one per chunk
    assert st.counts["drain"] == 1   # only the n×n result leaves


def test_prefetch_summary_event_in_eventlog(tmp_path):
    from marlin_tpu.utils.tracing import EventLog, set_default_event_log

    log = EventLog(str(tmp_path / "events.jsonl"))
    prev = set_default_event_log(log)
    try:
        a = np.ones((128, 4), np.float32)
        mt.streamed_gramian(a, chunk_rows=32, prefetch=True)
    finally:
        set_default_event_log(prev)
        log.close()
    kinds = [e["kind"] for e in log.read()]
    assert "prefetch" in kinds
    ev = next(e for e in log.read() if e["kind"] == "prefetch")
    assert ev["chunks"] == 4
    assert "produce_s" in ev and "stall_s" in ev


@pytest.mark.parametrize("op", ["matmul", "gramian"])
def test_a_streamed_op_leaves_its_stages_and_one_summary_and_no_more(
        op, tmp_path):
    """Seven whole chunks and a short tail: the stages count every chunk
    (the result's fetches differ: a chunk at a time for the product, once
    for the Gramian), the EventLog holds ONE ``prefetch`` summary, under
    the op's span, and no record of any other kind: a streamed op reports
    its stages, not a rate."""
    from marlin_tpu.utils.tracing import EventLog, set_default_event_log

    rng = np.random.default_rng(13)
    a = rng.standard_normal((7 * 64 + 10, 8)).astype(np.float32)
    b = rng.standard_normal((8, 5)).astype(np.float32)
    st = StageTimes()
    log = EventLog(str(tmp_path / "events.jsonl"))
    prev = set_default_event_log(log)
    try:
        if op == "matmul":
            got = mt.streamed_matmul(a, b, chunk_rows=64, stats=st)
            want = a @ b
        else:
            got = mt.streamed_gramian(a, chunk_rows=64, stats=st)
            want = a.T @ a
    finally:
        set_default_event_log(prev)
        log.close()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-3)
    assert {k: st.counts[k] for k in ("produce", "transfer", "compute")} \
        == {"produce": 8, "transfer": 8, "compute": 8}
    assert st.counts["drain"] == (8 if op == "matmul" else 1)
    assert st.seconds["compute"] > 0 and "stall" in st.seconds
    # (a process with the compile bridge installed also logs its compiles)
    recs = [r for r in log.read() if r["kind"] != "compile"]
    assert [r["kind"] for r in recs] == ["prefetch"]
    (ev,) = recs
    assert ev["chunks"] == 8 and ev["trace_id"]
    assert {"produce_s", "transfer_s", "stall_s"} <= set(ev)
    assert not _alive_workers()


# ------------------------------------------------------------- io loaders
def test_mnist_chunked_loader_matches_bulk(tmp_path):
    from marlin_tpu.io import mnist

    rng = np.random.default_rng(12)
    imgs = rng.integers(0, 256, (50, 7, 7), dtype=np.uint8)
    path = tmp_path / "images-idx3-ubyte"
    with open(path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, 50, 7, 7))
        f.write(imgs.tobytes())

    bulk = mnist.load_mnist_images(str(path))
    chunks = list(mnist.iter_mnist_image_chunks(str(path), chunk_rows=16))
    assert [c.shape[0] for c in chunks] == [16, 16, 16, 2]
    np.testing.assert_array_equal(np.concatenate(chunks), bulk)

    ooc = mnist.mnist_images_out_of_core(str(path), chunk_rows=16)
    assert ooc.shape == (50, 49)
    # streamed gramian over the file-backed source (prefetch on by default)
    np.testing.assert_allclose(ooc.gramian(), bulk.T @ bulk,
                               rtol=1e-4, atol=1e-4)


def test_mnist_truncated_file_fails_loudly(tmp_path):
    from marlin_tpu.io import mnist

    path = tmp_path / "trunc-idx3-ubyte"
    with open(path, "wb") as f:
        f.write(struct.pack(">IIII", 2051, 10, 4, 4))
        f.write(b"\x00" * (3 * 16))  # only 3 of 10 rows
    with pytest.raises(ValueError, match="truncated"):
        list(mnist.iter_mnist_image_chunks(str(path), chunk_rows=4))


def test_text_chunked_loader_rejects_gapped_and_malformed(tmp_path):
    from marlin_tpu.io import iter_matrix_file_chunks

    gapped = tmp_path / "gapped.txt"
    gapped.write_text("0:1.0,2.0\n2:3.0,4.0\n4:5.0,6.0\n")
    with pytest.raises(ValueError, match="contiguous"):
        list(iter_matrix_file_chunks(str(gapped), chunk_rows=2))

    no_colon = tmp_path / "bad.txt"
    no_colon.write_text("0:1.0,2.0\n1.0 2.0\n")
    with pytest.raises(ValueError, match="not row format"):
        list(iter_matrix_file_chunks(str(no_colon), chunk_rows=2))

    bad_idx = tmp_path / "badidx.txt"
    bad_idx.write_text("zero:1.0,2.0\n")
    with pytest.raises(ValueError, match="non-integer row index"):
        list(iter_matrix_file_chunks(str(bad_idx), chunk_rows=2))


def test_text_out_of_core_loader(tmp_path, mesh):
    from marlin_tpu.io import load_matrix_file_out_of_core, save_matrix

    rng = np.random.default_rng(13)
    arr = rng.standard_normal((17, 5)).astype(np.float32)
    path = str(tmp_path / "m.txt")
    save_matrix(mt.DenseVecMatrix.from_array(arr, mesh), path)

    ooc = load_matrix_file_out_of_core(path, chunk_rows=4)
    assert ooc.shape == (17, 5)
    np.testing.assert_allclose(ooc.multiply(np.eye(5, dtype=np.float32)), arr,
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(ooc.gramian(), arr.T @ arr,
                               rtol=1e-3, atol=1e-3)


# --------------------------------------------------------------- CPU smoke
def test_smoke_tiny_streamed_gramian_prefetch_cpu():
    """Fast tier-1 smoke: the full prefetch path (threads, device_put, jit
    accumulate, D2H drain) on a matrix small enough for any CPU run."""
    a = np.random.default_rng(14).standard_normal((96, 6)).astype(np.float32)
    g = mt.streamed_gramian(a, chunk_rows=32, prefetch=True)
    np.testing.assert_allclose(g, a.T @ a, rtol=1e-4, atol=1e-4)
    assert not _alive_workers()
