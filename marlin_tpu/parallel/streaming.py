"""Out-of-core streaming for matrices bigger than device HBM.

The reference spills oversized matrices via Spark's disk-backed RDDs
(SURVEY.md §7 hard parts: "Matrices bigger than the TPU pod's HBM: Marlin
spills via Spark; the rebuild needs host-offload streaming of blocks"). This
module is that layer for the tall-skinny workloads (BASELINE.md config 4:
10⁷×512 · 512×512): the tall operand lives on the host (numpy array, memmap, or
a chunk generator), row-chunks are streamed through device HBM, and either

- :func:`streamed_matmul` — each chunk is multiplied against a resident
  (replicated/sharded) right-hand side and the result streams back to host, or
- :func:`streamed_gramian` — AᵀA accumulates *on device* (the reference's
  Gramian aggregate, DenseVecMatrix.scala:1444-1486) and only the n×n result
  ever leaves.

Chunk production (source read, dtype conversion, ``_compress_for_transfer``,
H2D dispatch) runs on background threads through
:class:`~marlin_tpu.parallel.prefetch.ChunkPrefetcher` by default
(``config.prefetch_enabled``), so the upload of chunk i+1 overlaps device
compute of chunk i instead of serializing behind it; ``prefetch=False`` (or
the config flag) restores the synchronous loop. Results are bit-identical
either way — the prefetcher reorders *work*, never *math*.
"""

from __future__ import annotations

import time
from typing import Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..config import get_config
from ..obs import trace as obs_trace
from ..utils.profiling import StageTimes
from .prefetch import ChunkPrefetcher

__all__ = ["streamed_matmul", "streamed_gramian", "iter_row_chunks"]


# Module-level jits shared by every streamed call: a per-call `@jax.jit`
# closure is a fresh cache per invocation, so each streamed op would
# recompile its chunk programs EVERY time (found by the compile-count guard
# in tests/test_prefetch.py). Hoisted here, repeated streaming over the same
# chunk geometry hits one compiled program per shape, process-wide.

def _chunk_mm_impl(x, b_dev, precision):
    # re-expand compressed uploads without ever *down*-casting: promote to
    # the wider of the two dtypes (f32 a × bf16 b stays f32; bf16 uploads
    # widen to b's dtype)
    return jnp.dot(x.astype(jnp.promote_types(x.dtype, b_dev.dtype)), b_dev,
                   precision=precision)


_chunk_mm = jax.jit(_chunk_mm_impl, static_argnames=("precision",))


def _gram_accumulate_impl(g, x, precision):
    x = x.astype(g.dtype)
    return g + jnp.dot(x.T, x, precision=precision)


_gram_accumulate = jax.jit(_gram_accumulate_impl,
                           static_argnames=("precision",))


def iter_row_chunks(a, chunk_rows: int) -> Iterator[np.ndarray]:
    """Yield row chunks from an ndarray/memmap (zero-copy views)."""
    for start in range(0, a.shape[0], chunk_rows):
        yield a[start : start + chunk_rows]


def _as_chunks(a_source, chunk_rows: int) -> Iterable[np.ndarray]:
    if hasattr(a_source, "iter_chunks"):
        # a ChunkStore (io/chunkstore.py): native mmap'd reads at the
        # STREAMING chunk size — scatter/gather decouples it from the
        # on-disk chunk size. Checked before the array duck-type: a store
        # also has .shape, but slicing it per-chunk would lose the native
        # window gather.
        return a_source.iter_chunks(chunk_rows)
    if hasattr(a_source, "shape") and hasattr(a_source, "__getitem__"):
        return iter_row_chunks(a_source, chunk_rows)
    return a_source  # already an iterable of chunks


def _chunk_stream(a_source, chunk_rows: int, transfer_dtype, prefetch,
                  stats: StageTimes):
    """The shared front half of both streamed ops: an iterator of
    device-committed chunks, prefetched on background threads when enabled.

    Returns ``(iterator, closer)`` — ``closer()`` must run on every exit path
    (the prefetcher owns threads)."""
    chunks = _as_chunks(a_source, chunk_rows)

    def transform(c):
        # np.asarray first: list/sequence chunks become one array (device_put
        # of a bare list would treat it as a pytree of scalars)
        return _compress_for_transfer(np.asarray(c), transfer_dtype)

    enabled = get_config().prefetch_enabled if prefetch is None else prefetch
    if enabled:
        pf = ChunkPrefetcher(chunks, transform, stats=stats)
        return pf, pf.close
    # synchronous fallback: same read + transform + upload, on the caller's
    # thread ("produce" covers the source read too, matching the prefetcher's
    # accounting so on/off stage breakdowns are comparable)
    def sync_stream():
        import time

        it = iter(chunks)
        while True:
            t0 = time.perf_counter()
            try:
                c = next(it)
            except StopIteration:
                return
            c = transform(c)
            stats.add("produce", time.perf_counter() - t0)
            with stats.timed("transfer"):
                c = jax.device_put(c)
            yield c

    return sync_stream(), (lambda: None)


def streamed_matmul(
    a_source,
    b,
    chunk_rows: int = 1 << 18,
    out: np.ndarray | None = None,
    precision: str | None = None,
    transfer_dtype=None,
    prefetch: bool | None = None,
    stats: StageTimes | None = None,
) -> np.ndarray | None:
    """``A @ B`` where A streams through the device in row chunks.

    ``a_source``: ndarray/memmap or iterable of row-chunk ndarrays.
    ``b``: (k, n) array or DenseMatrix, resident on device.
    ``out``: optional preallocated (m, n) host array (e.g. a writable memmap)
    filled in place; otherwise chunks are collected and stacked (only sensible
    when the result fits host RAM).
    ``transfer_dtype="bfloat16"`` halves H2D bytes (host-side cast).
    ``prefetch``: None = follow ``config.prefetch_enabled``; True/False force
    the async pipeline on/off (results are identical either way).
    ``stats``: optional :class:`StageTimes` receiving the per-stage
    produce/transfer/stall/compute/drain breakdown.
    """
    precision = precision or get_config().matmul_precision
    stats = stats if stats is not None else StageTimes()
    b_dev = jnp.asarray(b.logical() if hasattr(b, "logical") else b)

    def chunk_mm(x):
        return _chunk_mm(x, b_dev, precision)

    results, offset, pending, saw_chunk = [], 0, [], False

    def drain(limit: int):
        nonlocal offset
        while len(pending) > limit:
            y = pending.pop(0)
            with stats.timed("drain"):
                y_np = np.asarray(jax.device_get(y))
            if out is not None:
                out[offset : offset + y_np.shape[0]] = y_np
            else:
                results.append(y_np)
            offset += y_np.shape[0]

    # one span per streamed op: the prefetcher's producer threads inherit it
    # (it is created inside), so the op's chunk records + close summary join
    # into one trace in the JSONL (docs/observability.md)
    with obs_trace.span("streamed_matmul"):
        stream, closer = _chunk_stream(a_source, chunk_rows, transfer_dtype,
                                       prefetch, stats)
        try:
            for x in stream:
                saw_chunk = True
                with stats.timed("compute"):
                    pending.append(chunk_mm(x))
                drain(1)  # keep one result in flight: overlap compute + D2H
            if not saw_chunk:
                raise ValueError("empty input stream")
            drain(0)
        finally:
            closer()
    return out if out is not None else np.concatenate(results, axis=0)


def _compress_for_transfer(chunk: np.ndarray, transfer_dtype) -> np.ndarray:
    """Cast on the *host* before upload — the point is halving the H2D bytes
    (the bottleneck of every streamed op), so the cast must not happen
    device-side."""
    if transfer_dtype is None:
        return chunk
    import ml_dtypes  # ships with jax

    np_dtype = np.dtype(
        {"bfloat16": ml_dtypes.bfloat16, "float16": np.float16}.get(
            str(transfer_dtype), transfer_dtype
        )
    )
    chunk = np.asarray(chunk)
    return chunk if chunk.dtype == np_dtype else chunk.astype(np_dtype)


def streamed_gramian(
    a_source,
    n_cols: int | None = None,
    chunk_rows: int = 1 << 18,
    precision: str | None = None,
    dtype=jnp.float32,
    transfer_dtype=None,
    prefetch: bool | None = None,
    stats: StageTimes | None = None,
) -> np.ndarray:
    """``AᵀA`` with A streamed in row chunks and the n×n accumulator resident
    on device — one rank-chunk ``syrk`` per chunk, no driver reduction.

    ``transfer_dtype="bfloat16"`` casts chunks on the host before upload,
    halving H2D traffic (the streamed paths' bottleneck) at bf16 input
    precision; accumulation stays in ``dtype`` (f32). ``prefetch``/``stats``
    as in :func:`streamed_matmul`."""
    precision = precision or get_config().matmul_precision
    stats = stats if stats is not None else StageTimes()

    def accumulate(g, x):
        return _gram_accumulate(g, x, precision)

    g = None
    # with no explicit transfer dtype, upload in the accumulation dtype (the
    # pre-existing contract: `dtype` governs both upload width and accumulator)
    effective_transfer = transfer_dtype if transfer_dtype is not None else dtype
    with obs_trace.span("streamed_gramian"):  # as in streamed_matmul
        stream, closer = _chunk_stream(a_source, chunk_rows,
                                       effective_transfer, prefetch, stats)
        try:
            for x in stream:
                if n_cols is not None and x.shape[1] != n_cols:
                    raise ValueError(
                        f"chunk has {x.shape[1]} cols, expected {n_cols}")
                if g is None:
                    n_cols = x.shape[1]
                    g = jnp.zeros((n_cols, n_cols), dtype)
                with stats.timed("compute"):
                    g = accumulate(g, x)
        finally:
            closer()
        if g is None:
            raise ValueError("empty input stream")
        with stats.timed("drain"):
            return np.asarray(jax.device_get(g))
