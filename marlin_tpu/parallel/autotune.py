"""Empirical multiply-strategy autotuning.

The reference picks its multiply execution statically: a broadcast-size
threshold plus the CARMA split heuristic (DenseVecMatrix.scala:196-231,
MTUtils.scala:150-175), and ships ``RMMcompare`` (examples/RMMcompare.scala)
so a human can time the candidates and pick by hand. This module makes that
comparison programmatic: time each viable engine on the real operands ONCE
per (shape, dtype, precision, mesh) configuration, cache the winner
in-process AND on disk (``config.autotune_cache_path``; winners survive
process restarts), and let ``multiply(strategy="tuned")`` consult the cache — an
empirical dispatch that beats any static heuristic wherever the heuristic's
model of the machine is wrong (e.g. dispatch-latency-bound mid sizes, or
meshes where resharding costs dominate).

Timing discipline: dispatch is async, so each candidate is compiled first,
then ``reps`` calls are enqueued back-to-back and forced once with a scalar
fetch — the same ``MTUtils.evaluate`` discipline the benchmarks use.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import os
import tempfile
import threading
import time

import jax.numpy as jnp

from .matmul import UnknownStrategyError

__all__ = ["tune_multiply", "best_strategy", "tune_gemm", "best_gemm",
           "tune_bsr", "best_bsr_strategy", "clear_cache"]

_CACHE: dict[tuple, str] = {}

_scratch_ids = itertools.count()


@contextlib.contextmanager
def _scratch_accounted(tag: str, nbytes: int):
    """Account tuning-time scratch — the candidate result buffer held live
    across the timing loop — in the process MemoryLedger (component
    ``autotune``) for exactly the measurement window. Accounting never
    fails a tune."""
    name = f"autotune:{tag}#{next(_scratch_ids)}"
    led = None
    try:
        from ..obs.memledger import get_ledger

        led = get_ledger()
        led.register(name, max(int(nbytes), 0), "autotune")
    except Exception:
        led = None
    try:
        yield
    finally:
        if led is not None:
            try:
                led.free(name, strict=False)
            except Exception:
                pass

# Disk layer: tuned winners persist across process restarts (timing a full
# candidate set costs seconds at production sizes — paying it once per
# machine, not once per process, is the point). Keyed by the stringified
# in-memory key, which carries shapes, both operands' layouts/specs, dtypes,
# precision, mesh shape (device count), backend platform AND device kind —
# a cache entry can never leak across a hardware or layout change (platform
# alone says "tpu", which would replay a v4-tuned winner on a v5p). Entries
# are timings' *winners* only; they are machine-specific by design, hence
# the local path.
_DISK_LOCK = threading.Lock()
_disk: dict[str, str] | None = None  # lazily loaded; path tracked for reloads
_disk_path_loaded: str | None = None

# Cache-file schema version, stored as an int under "__version__" in the
# same flat dict as the winners (str-valued keys only otherwise, so loads
# can filter it out). Bumped when the KEY layout changes — v2 added
# device_kind — so a file persisted by an older layout is ignored wholesale
# rather than silently replaying winners under now-ambiguous keys.
_DISK_VERSION = 2


def _disk_path() -> str | None:
    """Resolved persistence path; None when disabled (config path "")."""
    from ..config import get_config

    p = get_config().autotune_cache_path
    if p == "":
        return None
    if p is None:
        return os.path.join(os.path.expanduser("~"), ".cache", "marlin_tpu",
                            "autotune.json")
    return p


def _disk_layer() -> dict[str, str]:
    """The persisted winners, (re)loaded when first touched or when the
    configured path changed. Unreadable/corrupt files degrade to empty —
    autotune must never fail a multiply over a cache file."""
    global _disk, _disk_path_loaded
    path = _disk_path()
    if path is None:
        return {}
    if _disk is None or _disk_path_loaded != path:
        try:
            with open(path) as f:
                data = json.load(f)
            if data.get("__version__") != _DISK_VERSION:
                # a pre-versioned or older-layout file: its keys don't mean
                # what this version's keys mean — drop it (one re-tune per
                # configuration, never a wrong winner)
                _disk = {}
            else:
                _disk = {k: v for k, v in data.items()
                         if isinstance(v, str)}
        except (OSError, ValueError):
            _disk = {}
        _disk_path_loaded = path
    return _disk


def _persist(key: tuple, strategy: str) -> None:
    """Merge one winner into the disk layer atomically (tmp + rename, the
    io.checkpoint discipline — a torn write must not corrupt the cache).
    Merge-on-write: the file is re-read under a lock before writing so
    concurrent writers' freshly persisted winners are kept. Threads share
    ``_DISK_LOCK``; concurrent *processes* are serialized by a best-effort
    ``fcntl`` lock on a sidecar file (POSIX only — elsewhere a true
    simultaneous cross-process race can still drop a key, costing one
    re-tune on that process's next restart, never a corrupt file)."""
    global _disk
    path = _disk_path()
    if path is None:
        return
    with _DISK_LOCK:
        try:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        except OSError:
            return  # read-only FS: in-process cache still works
        lock_f = None
        try:
            try:
                import fcntl

                lock_f = open(path + ".lock", "w")
                fcntl.flock(lock_f, fcntl.LOCK_EX)
            except (ImportError, OSError):
                lock_f = None  # non-POSIX / unlockable: best effort
            _disk = None  # force a fresh read: pick up other processes' writes
            layer = _disk_layer()
            layer[repr(key)] = strategy
            try:
                fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                           suffix=".tmp")
                with os.fdopen(fd, "w") as f:
                    json.dump({"__version__": _DISK_VERSION, **layer}, f,
                              indent=1, sort_keys=True)
                os.replace(tmp, path)
            except OSError:
                pass
        finally:
            if lock_f is not None:
                lock_f.close()


def _operand_meta(other):
    """(shape, dtype, spec) of the right operand — spec present only for
    distributed matrices (a raw array has no layout of its own)."""
    shape = getattr(other, "shape", None) or jnp.asarray(other).shape
    dtype = getattr(getattr(other, "data", other), "dtype", jnp.float32)
    spec = tuple(getattr(other, "spec", ()) or ())
    return tuple(shape), dtype, spec


def _cache_key(mat, other, precision):
    """Layouts matter as much as shapes: a row-sharded and a block-sharded
    pair of the same shape reshard differently per strategy, so both operands'
    specs (and the matrix class) are part of the key. Hardware identity is
    platform AND device_kind — "tpu" alone would replay a winner tuned on
    one TPU generation on another whose MXU/VMEM balance is different."""
    other_shape, other_dtype, other_spec = _operand_meta(other)
    mesh = mat.mesh
    dev = mesh.devices.flat[0]
    return (
        type(mat).__name__,
        mat.shape,
        tuple(mat.spec),
        other_shape,
        other_spec,
        str(mat.data.dtype),
        str(other_dtype),
        precision,
        tuple(sorted(mesh.shape.items())),
        dev.platform,
        getattr(dev, "device_kind", ""),
    )


def _candidates(mat, other_shape, other_itemsize) -> list[str]:
    """Viable engines for this problem: always gspmd + rmm + ring; the two
    broadcast forms only when the replicated operand is within 4x the
    configured threshold (beyond that the replication alone disqualifies them
    — no point timing a guaranteed loser). Each operand is sized with its OWN
    itemsize."""
    from ..config import get_config

    m, k = mat.shape
    n = other_shape[1]
    a_itemsize = jnp.dtype(mat.data.dtype).itemsize
    threshold = 4 * get_config().broadcast_threshold_mb
    cands = ["gspmd", "rmm", "ring"]
    if k * n * other_itemsize / 1e6 <= threshold:
        cands.append("broadcast")
    if m * k * a_itemsize / 1e6 <= threshold:
        cands.append("broadcast_a")
    return cands


def tune_multiply(mat, other, strategies=None, reps: int = 3,
                  precision: str | None = None) -> list[tuple[str, float]]:
    """Time each candidate strategy for ``mat.multiply(other)`` on the live
    mesh and return ``[(strategy, seconds_per_multiply), ...]`` sorted
    fastest-first.

    With the default (full) candidate set, the winner is cached so
    ``strategy="tuned"`` multiplies of the same configuration dispatch
    straight to it; an explicit ``strategies`` subset times those engines
    only and does NOT touch the cache (a subset winner must never pin the
    tuned dispatch)."""
    from ..utils.profiling import evaluate

    other_shape, other_dtype, _ = _operand_meta(other)
    if len(other_shape) != 2:
        raise ValueError(
            f"tune_multiply needs a 2-D right operand, got shape {other_shape}"
            " — matrix @ vector dispatch does not go through the tuner"
        )
    if mat.shape[1] != other_shape[0]:
        raise ValueError(
            f"inner dim mismatch: {mat.shape} @ {other_shape}"
        )
    explicit = strategies is not None
    if not explicit:
        strategies = _candidates(mat, other_shape,
                                 jnp.dtype(other_dtype).itemsize)
    m, k = mat.shape
    n = other_shape[1]
    a_item = jnp.dtype(mat.data.dtype).itemsize
    b_item = jnp.dtype(other_dtype).itemsize
    results = []
    with _scratch_accounted(f"multiply:{m}x{k}x{n}",
                            m * n * max(a_item, b_item)):
        for s in strategies:
            try:
                c = mat.multiply(other, strategy=s,
                                 precision=precision)  # compile
                evaluate(c)
                t0 = time.perf_counter()
                for _ in range(reps):
                    c = mat.multiply(other, strategy=s, precision=precision)
                evaluate(c)
                elapsed = time.perf_counter() - t0
                results.append((s, elapsed / reps))
            except UnknownStrategyError:
                # an engine rejecting the strategy name is a skippable
                # candidate; any other ValueError is a genuinely broken run
                # (layout/shape validation inside an engine) and must
                # surface
                continue
    if not results:
        raise ValueError("no viable multiply strategy could be timed")
    results.sort(key=lambda kv: kv[1])
    if not explicit:
        key = _cache_key(mat, other, precision)
        _CACHE[key] = results[0][0]
        _persist(key, results[0][0])
    return results


def best_strategy(mat, other, precision: str | None = None) -> str:
    """Cached winner for this configuration — memory layer first, then the
    on-disk layer (winners survive process restarts), tuning only on a miss
    in both."""
    from .matmul import _STRATEGIES

    key = _cache_key(mat, other, precision)
    if key not in _CACHE:
        with _DISK_LOCK:
            persisted = _disk_layer().get(repr(key))
        # validate against the live strategy set: a file written by an older
        # version (renamed/removed engine) or hand-edited must degrade to a
        # retune, never poison every tuned multiply of this configuration
        if persisted in _STRATEGIES:
            _CACHE[key] = persisted
        else:
            tune_multiply(mat, other, precision=precision)
    return _CACHE[key]


# --------------------------------------------------------------------------
# Generated-family tuners (ops/tile_family.py): the same two-layer cache and
# measured-time ranking as tune_multiply, applied to kernel families instead
# of distributed-multiply engines. tune_multiply picks WHICH engine runs a
# sharded multiply; these pick WHICH generated tiling (or formulation) runs
# one local kernel — "Automatic Generators for a Family of Matrix
# Multiplication Routines" (2310.20347): enumerate + prune analytically
# (tile_family), then measure and persist the winner per device kind.


def _device_sig() -> tuple[str, str]:
    """(platform, device_kind) of the default device — the hardware half of
    every local-kernel cache key (local kernels have no mesh to ask)."""
    import jax

    d = jax.devices()[0]
    return d.platform, getattr(d, "device_kind", "")


def _gemm_key(m: int, k: int, n: int, dtype) -> tuple:
    return ("gemm", (int(m), int(k), int(n)), str(dtype), *_device_sig())


def _time_candidates(program: str, candidates, run, reps: int,
                     scratch_bytes: int = 0):
    """Shared measurement loop: compile, time ``reps`` back-to-back calls
    (utils.profiling.evaluate forces true completion) and rank by measured
    time. A candidate that fails to build/run is skipped, not fatal (the
    family generator can propose a tile the backend rejects).
    ``scratch_bytes`` accounts the tuning window's result-buffer residency
    in the memory ledger."""
    from ..utils.profiling import evaluate

    results = []
    with _scratch_accounted(program, scratch_bytes) if scratch_bytes \
            else contextlib.nullcontext():
        for name in candidates:
            try:
                evaluate(run(name))  # compile outside the timed window
                t0 = time.perf_counter()
                out = None
                for _ in range(reps):
                    out = run(name)
                evaluate(out)
                elapsed = time.perf_counter() - t0
            except Exception:
                continue
            results.append((name, elapsed / reps))
    if not results:
        raise ValueError(f"no {program} candidate could be timed")
    results.sort(key=lambda kv: kv[1])
    return results


def tune_gemm(a, b, candidates=None, reps: int = 3) -> list[tuple[str, float]]:
    """Time the XLA dot against the generated ``pallas_matmul`` tiling
    family for the local ``a @ b`` and return ``[(candidate, seconds)]``
    fastest-first. Default candidates come from
    :func:`~marlin_tpu.ops.tile_family.gemm_candidates` (VMEM-pruned,
    traffic-ranked) plus ``"xla"``; the winner is cached (memory + disk,
    device_kind-keyed) for :func:`best_gemm`. An explicit ``candidates``
    subset is timed without touching the cache, as in
    :func:`tune_multiply`."""
    from ..ops import tile_family
    from ..ops.local import gemm as xla_gemm
    from ..ops.pallas_kernels import pallas_matmul

    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"inner dim mismatch: {a.shape} @ {b.shape}")
    m, k = a.shape
    n = b.shape[1]
    item = jnp.dtype(a.dtype).itemsize
    explicit = candidates is not None
    if candidates is None:
        candidates = ["xla"] + [c.name for c in
                                tile_family.gemm_candidates(m, k, n, item)]

    def run(name):
        if name == "xla":
            return xla_gemm(a, b)
        t = tile_family.parse_gemm_candidate(name)
        return pallas_matmul(a, b, bm=t.bm, bn=t.bn, bk=t.bk)

    results = _time_candidates("gemm", candidates, run, reps,
                               scratch_bytes=m * n * item)
    if not explicit:
        key = _gemm_key(m, k, n, a.dtype)
        _CACHE[key] = results[0][0]
        _persist(key, results[0][0])
    return results


def _valid_gemm_name(name) -> bool:
    if name == "xla":
        return True
    try:
        from ..ops import tile_family

        tile_family.parse_gemm_candidate(name)
        return True
    except (TypeError, ValueError):
        return False


def best_gemm(a, b, reps: int = 3) -> str:
    """Cached winning gemm candidate for these operands' configuration
    (``"xla"`` or ``"pallas:BMxBNxBK"``), tuning on a miss in both cache
    layers. Persisted names are validated before trust, exactly as
    :func:`best_strategy` validates engine names."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    key = _gemm_key(a.shape[0], a.shape[1], b.shape[1], a.dtype)
    if key not in _CACHE:
        with _DISK_LOCK:
            persisted = _disk_layer().get(repr(key))
        if _valid_gemm_name(persisted):
            _CACHE[key] = persisted
        else:
            tune_gemm(a, b, reps=reps)
    return _CACHE[key]


def _bsr_key(bsr, p: int, out_dtype) -> tuple:
    return ("bsr", bsr.shape, bsr.block_size, bsr.nnzb, int(p),
            str(out_dtype), *_device_sig())


def tune_bsr(bsr, b, candidates=None, reps: int = 2) -> list[tuple[str, float]]:
    """Time the BSR SpMM family (chunked-XLA ``chunk_blocks`` variants +
    the Pallas kernel, :func:`~marlin_tpu.ops.tile_family.bsr_candidates`)
    for ``bsr @ b`` and return ``[(candidate, seconds)]`` fastest-first,
    caching the winner for :func:`best_bsr_strategy`. This is what
    guarantees the hand-written kernel can never be dispatched where the
    XLA formulation wins — the ranking, not a human, picks."""
    from ..ops import tile_family

    arr = jnp.asarray(b.logical() if hasattr(b, "logical") else b)
    p = arr.shape[1] if arr.ndim == 2 else 1
    item = jnp.dtype(arr.dtype).itemsize
    explicit = candidates is not None
    if candidates is None:
        candidates = tile_family.bsr_candidates(bsr.block_size, bsr.nnzb, p,
                                                item)

    def run(name):
        cb = tile_family.parse_bsr_candidate(name)
        if cb is None:
            return bsr.multiply(arr, backend="pallas")
        return bsr.multiply(arr, chunk_blocks=cb)

    results = _time_candidates("bsr_spmm", candidates, run, reps,
                               scratch_bytes=bsr.shape[0] * p * item)
    if not explicit:
        key = _bsr_key(bsr, p, arr.dtype)
        _CACHE[key] = results[0][0]
        _persist(key, results[0][0])
    return results


def _valid_bsr_name(name) -> bool:
    try:
        from ..ops import tile_family

        tile_family.parse_bsr_candidate(name)
        return True
    except (TypeError, ValueError):
        return False


def best_bsr_strategy(bsr, b, reps: int = 2) -> str:
    """Cached winning BSR candidate (``"chunked:N"`` or ``"pallas"``) for
    this (shape, block structure, panel width, device) configuration,
    tuning on a miss — the consultation point for
    ``matrix/sparse.py``'s ``backend="auto"`` dispatch."""
    arr = jnp.asarray(b.logical() if hasattr(b, "logical") else b)
    p = arr.shape[1] if arr.ndim == 2 else 1
    key = _bsr_key(bsr, p, arr.dtype)
    if key not in _CACHE:
        with _DISK_LOCK:
            persisted = _disk_layer().get(repr(key))
        if _valid_bsr_name(persisted):
            _CACHE[key] = persisted
        else:
            tune_bsr(bsr, b, reps=reps)
    return _CACHE[key]


def clear_cache() -> None:
    """Clear BOTH layers: the in-process dict and the persisted file."""
    global _disk, _disk_path_loaded
    _CACHE.clear()
    with _DISK_LOCK:
        _disk, _disk_path_loaded = None, None
        path = _disk_path()
        if path is not None:
            for p in (path, path + ".lock"):
                try:
                    os.remove(p)
                except OSError:
                    pass
