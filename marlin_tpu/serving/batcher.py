"""Shape bucketing and per-bucket claim queues for the serving engine.

The serving programs compile one XLA executable per *shape* — slot width B,
padded prompt P, decode steps S are all baked in. Serving traffic is
ragged, so without discipline every new shape pays a fresh multi-second
compile. The discipline here:

- **Buckets** — a small static set of ``(P_bucket, steps_bucket)`` pairs. A
  request pads its prompt up to the smallest fitting ``P_bucket``; rows
  retire at their *requested* steps (the bucket only sizes the cache
  extent).
- **Fixed slot width** — every bucket's row set is exactly ``max_batch``
  wide (free rows run masked-harmless dummies), so B never varies and the
  compile count is bounded by the bucket set, not the traffic pattern.
- **Claim queues** — :class:`BatchFormer` keeps one priority-ordered FIFO
  per bucket; :meth:`BatchFormer.take_for_bucket` hands freed rows the best
  pending request immediately (prefill-on-admit — higher ``priority``
  first, FIFO among equals; sampling knobs never partition anything, they
  are per-row traced vectors in the decode programs). The gang scheduler's
  batch-forming machinery (sampling-knob grouping, ``max_wait`` ripening,
  ``next_batch``) was retired with it in PR 8 — paging superseded the gang
  fallback.
- **Warmup** — :func:`warmup_buckets` compiles the slab scheduler's
  prefill/decode-step pair per bucket before traffic (paged engines warm
  through :func:`~.kvpool.warmup_paged` instead — the engine's
  ``warmup()`` picks); :func:`aot_compile_buckets` compiles the same
  programs against a compile-only TPU topology (:mod:`marlin_tpu.utils
  .aot` — no chip needed) and returns the compiler's per-bucket peak-HBM
  accounting, the offline sizing channel for ``serve_buckets`` /
  ``serve_max_batch`` (paged pools size by page arithmetic instead:
  ``models/planner.kv_page_bytes`` × ``serve_num_pages``).

:class:`SlotPool` tracks the dense-slab backend's per-bucket state
(``serve_paged=False``): a persistent device-resident KV slab of
``max_batch`` slots plus the per-row vectors its decode program takes. The
paged backend's analog lives in :mod:`.kvpool` (:class:`~.kvpool
.PagedGroup`).
"""

from __future__ import annotations

import collections
from typing import Iterable, Sequence

import numpy as np

__all__ = ["normalize_buckets", "pick_bucket", "bucket_kv_bytes",
           "BatchFormer", "SlotPool", "warmup_buckets",
           "aot_compile_buckets", "bucket_program_key",
           "capture_bucket_costs"]

Bucket = tuple[int, int]  # (P_bucket, steps_bucket)


def normalize_buckets(buckets: Iterable[Sequence[int]]) -> tuple[Bucket, ...]:
    """Validate and sort a bucket set ascending by (P, steps) — the order
    :func:`pick_bucket` scans, so "smallest fitting bucket" is first hit."""
    out = []
    for b in buckets:
        p, s = int(b[0]), int(b[1])
        if p < 1 or s < 1:
            raise ValueError(f"bucket dims must be >= 1, got {(p, s)}")
        out.append((p, s))
    if not out:
        raise ValueError("at least one (P, steps) bucket is required")
    if len(set(out)) != len(out):
        raise ValueError(f"duplicate buckets in {out}")
    return tuple(sorted(out))


def pick_bucket(prompt_len: int, steps: int,
                buckets: Sequence[Bucket]) -> Bucket | None:
    """The smallest bucket holding a ``prompt_len``-token prompt generating
    ``steps`` tokens, or None when nothing fits (an admission rejection —
    better than a surprise compile)."""
    for p, s in buckets:
        if prompt_len <= p and steps <= s:
            return (p, s)
    return None


def bucket_kv_bytes(params: dict, heads: int, bucket: Bucket,
                    compute_dtype=None, batch: int = 1) -> int:
    """Per-request KV-cache bytes for one bucket row (times ``batch``): the
    decode working set is layers x 2 x max_len x kv_heads x dh in the compute
    dtype, and max_len = P + steps. This is the admission-control cost model
    — the cache IS the decode memory (models/transformer.py), so bounding the
    summed row cost bounds what a burst of admissions can pin in HBM. The
    charge is taken at admission (reserving the slot the request WILL
    occupy) and must be released on every retirement path — ok, expired,
    error, shutting_down — or admission wedges permanently
    (tests/test_serving.py guards this)."""
    import jax.numpy as jnp

    from ..models.transformer import _n_layers

    p, s = bucket
    d = params["emb"].shape[1]
    dh = d // heads
    kv_dim = params["l0"]["wk"].shape[1]  # kv_heads * dh (GQA-aware)
    dt = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype
    return _n_layers(params) * 2 * (p + s) * (kv_dim // dh) * dh \
        * dt.itemsize * batch


class _Group:
    """One bucket's stream of pending entries, kept in dispatch order:
    higher priority first, FIFO among equals (stable sort on a monotonic
    sequence number keeps arrival order)."""

    def __init__(self):
        self.entries: list = []  # (-priority, seq, entry)

    def add(self, entry, seq: int) -> None:
        self.entries.append((-entry.request.priority, seq, entry))
        self.entries.sort(key=lambda t: t[:2])

    def take(self, n: int):
        taken = [e for _, _, e in self.entries[:n]]
        del self.entries[:n]
        return taken


class BatchFormer:
    """One priority-ordered claim queue per bucket. Sampling knobs never
    partition anything — they are per-row traced vectors in the decode
    programs, so ANY mix shares a step (the gang scheduler's sampling-knob
    grouping and ``max_wait`` ripening retired with it, PR 8; ``max_wait``
    is still accepted and ignored so old call sites don't break). Not
    thread-safe by itself — the engine calls it under its own condition
    lock (one mutator, one reader)."""

    def __init__(self, buckets: Sequence[Bucket], max_batch: int,
                 max_wait: float = 0.0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_wait < 0:
            raise ValueError(f"max_wait must be >= 0, got {max_wait}")
        self.buckets = normalize_buckets(buckets)
        self.max_batch = max_batch
        self.max_wait = max_wait  # legacy knob: nothing ripens anymore
        self._groups: dict[Bucket, _Group] = collections.defaultdict(_Group)
        self._seq = 0

    def add(self, entry) -> None:
        """File one admitted entry under its bucket. ``entry.bucket`` and
        ``entry.enq_t`` were set at admission (engine.submit)."""
        self._groups[entry.bucket].add(entry, self._seq)
        self._seq += 1

    def pending(self) -> int:
        return sum(len(g.entries) for g in self._groups.values())

    def take_all(self) -> list:
        """Drain every pending entry (close() path — they get ShuttingDown
        results, never a decode)."""
        out = []
        for g in self._groups.values():
            out.extend(g.take(len(g.entries)))
        return out

    def pending_buckets(self) -> set:
        """Buckets that currently have pending entries (which groups might
        claim work this iteration)."""
        return {b for b, g in self._groups.items() if g.entries}

    def take_for_bucket(self, bucket: Bucket, n: int) -> list:
        """Up to ``n`` entries bound for ``bucket`` in dispatch order —
        the prefill-on-admit path: a freed row takes the best pending
        request immediately."""
        return self._groups[bucket].take(n) if bucket in self._groups else []


class SlotPool:
    """Slot bookkeeping for one bucket's persistent KV slab (row-level
    scheduling, docs/serving.md): which slot holds which entry, the per-row
    vectors the decode-step program takes (positions, emitted-step counts,
    sampling knobs), and the device-resident ``caches``/``tokens`` slab
    state itself (:func:`~marlin_tpu.models.transformer.init_kv_slab`; the
    engine replaces both references after every donated prefill/decode
    call). Single-threaded — only the engine worker touches a pool."""

    def __init__(self, params: dict, heads: int, bucket: Bucket, width: int,
                 compute_dtype: str | None = None):
        import jax.numpy as jnp

        from ..models.transformer import init_kv_slab

        p, s = bucket
        self.bucket = bucket
        self.width = width
        self.max_len = p + s
        self.caches = init_kv_slab(params, width, self.max_len, heads,
                                   compute_dtype)
        self.tokens = jnp.zeros((width, self.max_len), jnp.int32)
        self.entries: list = [None] * width
        # decode-program inputs; free slots keep position 0 (a harmless
        # dummy step inside their own row — see lm_decode_rows)
        self.positions = np.zeros(width, np.int32)
        self.steps_done = np.zeros(width, np.int32)
        self.lengths = np.zeros(width, np.int32)
        self.seeds = np.zeros(width, np.uint32)
        self.temperature = np.zeros(width, np.float32)
        self.top_p = np.ones(width, np.float32)   # 1.0 = nucleus filter off
        self.top_k = np.zeros(width, np.int32)    # 0 = rank filter off
        self.ttft_s = [None] * width

    def live_slots(self) -> list[int]:
        return [i for i, e in enumerate(self.entries) if e is not None]

    def free_slots(self) -> list[int]:
        return [i for i, e in enumerate(self.entries) if e is None]

    def occupancy(self) -> float:
        return len(self.live_slots()) / self.width

    def assign(self, slot: int, entry) -> None:
        """Bind an admitted entry to a freed slot: after the slot's prefill
        lands, the row's position is its first emitted token (= prompt
        length) and its sampling vectors come from the request."""
        r = entry.request
        self.entries[slot] = entry
        n = r.prompt.shape[0]
        self.lengths[slot] = n
        self.positions[slot] = n          # index of the last written token
        self.steps_done[slot] = 1         # prefill emitted the first token
        self.seeds[slot] = np.uint32(r.seed)
        self.temperature[slot] = r.temperature
        self.top_p[slot] = 1.0 if r.top_p is None else r.top_p
        self.top_k[slot] = 0 if r.top_k is None else r.top_k
        self.ttft_s[slot] = None

    def release(self, slot: int) -> None:
        """Free a slot on ANY retirement path (the stale cache/token row is
        fully overwritten by the next occupant's prefill)."""
        self.entries[slot] = None
        self.positions[slot] = 0
        self.steps_done[slot] = 0
        self.lengths[slot] = 0
        self.temperature[slot] = 0.0
        self.top_p[slot] = 1.0
        self.top_k[slot] = 0
        self.ttft_s[slot] = None


def _dummy_batch(bucket: Bucket, batch: int):
    """An inert full-width batch for a bucket: 1-token rows of token 0."""
    p, s = bucket
    prompts = np.zeros((batch, p), np.int32)
    lengths = np.ones((batch,), np.int32)
    return prompts, lengths


def bucket_program_key(params: dict, bucket: Bucket, max_batch: int,
                       compute_dtype=None) -> str:
    """The roofline-accounting key for one bucket's compiled programs
    (obs/perf.py). Capture sites (warmup/AOT/pool creation) and measurement
    sites (the engine's step/prefill timings) MUST both build the key here,
    or the cost/timing join silently misses."""
    import jax.numpy as jnp

    from ..obs import perf

    p, s = bucket
    dt = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype
    # the model geometry is part of the program identity: two models with
    # the same bucket/width/dtype compile different programs with different
    # costs, and their entries must not collide
    v, d = params["emb"].shape
    try:
        from ..models.transformer import _n_layers

        layers = _n_layers(params)
    except Exception:
        layers = "?"
    return perf.program_key(bucket=f"{p}x{s}", rows=max_batch, dtype=dt.name,
                            model=f"v{v}d{d}l{layers}")


def capture_bucket_costs(params: dict, heads: int, bucket: Bucket,
                         max_batch: int, compute_dtype: str | None = None,
                         moe: tuple | None = None,
                         key: str | None = None) -> None:
    """Capture the XLA cost model (flops, bytes accessed) of a bucket's
    slab program pair into the process :class:`~marlin_tpu.obs.perf
    .ProgramCosts` registry — trace + lower only (no backend compile; the
    bucket's real compile already happened or is about to through the jit
    cache). Gated per (program, bucket key) so repeated calls — the engine
    invokes this on every pool creation — cost two dict lookups after the
    first. Callers on the dispatch path pass their cached ``key`` (the
    engine's ``_prog_key``) so the gate really is that cheap — rebuilding
    it walks the params tree. Never raises: cost capture is observability
    and must not fail warmup or a dispatch. The paged pair captures through
    :func:`~.kvpool.capture_paged_costs`."""
    import jax

    from ..obs import perf

    costs = perf.get_program_costs()
    if key is None:
        key = bucket_program_key(params, bucket, max_batch, compute_dtype)
    programs = ("lm_prefill_slot", "lm_decode_rows")
    # gate on attempted, not succeeded: a backend without cost_analysis()
    # must not re-pay this trace+lower on every dispatch
    if all(costs.tried(name, key) for name in programs):
        return
    import jax.numpy as jnp

    from ..models.transformer import (_lm_decode_rows_jit,
                                      _lm_prefill_slot_jit, init_kv_slab)

    def st(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    sds = lambda tree: jax.tree.map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype), tree)
    p, s = bucket
    try:
        caches = sds(jax.eval_shape(
            lambda pp: init_kv_slab(pp, max_batch, p + s, heads,
                                    compute_dtype), params))
        tokens = st((max_batch, p + s))
        pre = _lm_prefill_slot_jit.trace(
            sds(params), caches, tokens, st(()), st((p,)), st(()),
            st((), jnp.uint32), st((), jnp.float32),
            st((), jnp.float32), st(()), heads=heads, max_len=p + s,
            compute_dtype=compute_dtype, moe=moe).lower()
        dec = _lm_decode_rows_jit.trace(
            sds(params), caches, tokens, st((max_batch,)),
            st((max_batch,)), st((max_batch,), jnp.uint32),
            st((max_batch,), jnp.float32),
            st((max_batch,), jnp.float32), st((max_batch,)),
            heads=heads, max_len=p + s, compute_dtype=compute_dtype,
            moe=moe).lower()
        costs.capture("lm_prefill_slot", key, lowered=pre)
        costs.capture("lm_decode_rows", key, lowered=dec)
    except Exception:
        # even a failed trace marks the attempt — never retry per dispatch
        for name in programs:
            costs.capture(name, key)


def warmup_buckets(params: dict, heads: int, buckets: Sequence[Bucket],
                   max_batch: int, compute_dtype: str | None = None,
                   moe: tuple | None = None) -> int:
    """Compile (and execute once, on dummy rows) every bucket's dense-slab
    program pair — slot-targeted prefill and the single-token decode step
    over a throwaway slab — so the first real request never pays the
    compile. Sampling knobs are per-row traced, so the two programs are
    the whole slab compile story (docs/serving.md); paged engines warm
    through :func:`~.kvpool.warmup_paged` against their live pool instead.
    Returns the buckets warmed."""
    import jax

    from ..models.transformer import lm_decode_rows, lm_prefill_slot

    buckets = normalize_buckets(buckets)
    for bucket in buckets:
        p, s = bucket
        prompts, _ = _dummy_batch(bucket, max_batch)
        # roofline accounting: the bucket's XLA cost model lands in the
        # process ProgramCosts registry alongside the warmup compile
        capture_bucket_costs(params, heads, bucket, max_batch,
                             compute_dtype, moe)
        pool = SlotPool(params, heads, bucket, max_batch, compute_dtype)
        caches, tokens, _ = lm_prefill_slot(
            params, pool.caches, pool.tokens, 0, prompts[0], 1,
            heads=heads, max_len=p + s, compute_dtype=compute_dtype,
            moe=moe)
        caches, tokens, nxt = lm_decode_rows(
            params, caches, tokens, pool.positions, pool.steps_done,
            pool.seeds, pool.temperature, pool.top_p, pool.top_k,
            heads=heads, max_len=p + s, compute_dtype=compute_dtype,
            moe=moe)
        jax.block_until_ready(nxt)
    return len(buckets)


def _peak_bytes(ma) -> int:
    """Peak device bytes from a ``memory_analysis()`` result. Some PJRT
    builds expose ``peak_memory_in_bytes``; where the stats object lacks it
    (the repo's getattr-guarded jaxlib-variance convention), fall back to
    the documented lower bound temp + argument + output bytes."""
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is not None:
        return int(peak)
    return int(ma.temp_size_in_bytes + ma.argument_size_in_bytes
               + ma.output_size_in_bytes)


def planner_ratio_warning(bucket: Bucket, peak_bytes: int,
                          planner_bytes: int,
                          factor: float = 2.0) -> str | None:
    """Planner honesty check: the warning text when the compiler's own peak
    accounting for a bucket exceeds the planner's slab arithmetic
    (``bucket_kv_bytes`` at full batch) by more than ``factor``, else
    ``None``. Pure so tests pin the threshold without a TPU: a ratio this
    far above 1.0 means the planner's admission budget is not the number
    HBM will actually see, and ``serve_max_batch`` sized from it will OOM
    under load."""
    if planner_bytes <= 0:
        return None
    ratio = peak_bytes / planner_bytes
    if ratio <= factor:
        return None
    return (f"bucket {bucket}: compiler peak {peak_bytes} B is "
            f"{ratio:.1f}x the planner's {planner_bytes} B slab "
            f"arithmetic — size serve_buckets/serve_max_batch from the "
            f"measured peak, not the planner (docs/serving.md, bucket "
            f"tuning)")


def aot_compile_buckets(params: dict, heads: int, buckets: Sequence[Bucket],
                        max_batch: int, compute_dtype: str | None = None,
                        moe: tuple | None = None,
                        topology_name: str = "v5e:2x2"
                        ) -> dict[Bucket, int]:
    """Compile every bucket's program(s) against a compile-only TPU
    topology (no chip; :mod:`marlin_tpu.utils.aot`) and return
    ``{bucket: peak_hbm_bytes}`` from the compiler's own accounting — the
    offline evidence for sizing ``serve_buckets`` x ``serve_max_batch``
    against :func:`~marlin_tpu.models.planner.usable_hbm_bytes` (the same
    budget the admission gate enforces at runtime). Compiles the dense-slab
    backend's program pair (slot prefill + decode step) and reports the
    larger peak, warning (``RuntimeWarning``) when that peak exceeds the
    planner's slab arithmetic by more than 2x
    (:func:`planner_ratio_warning`). Sizing rule: every bucket's persistent
    slab stays
    device-resident simultaneously (the engine never frees a pool), so
    steady-state HBM is the SUM over buckets of ``bucket_kv_bytes(...,
    batch=max_batch)`` plus the largest per-bucket program peak reported
    here — not the largest bucket alone. The paged backend sizes by page
    arithmetic instead: ``serve_num_pages`` x
    :func:`~marlin_tpu.models.planner.kv_page_bytes` IS its steady-state
    cache footprint, whatever the bucket set (docs/serving.md, bucket
    tuning). Requires libtpu
    (:func:`~marlin_tpu.utils.aot.tpu_topology`). Peak accounting
    degrades to the temp+argument+output lower bound on PJRT builds whose
    stats object lacks ``peak_memory_in_bytes`` (:func:`_peak_bytes`)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ..config import config_context
    from ..models.transformer import (_lm_decode_rows_jit,
                                      _lm_prefill_slot_jit, init_kv_slab)
    from ..utils.aot import topology_mesh

    mesh = topology_mesh(("rows",), (1,), topology_name=topology_name)
    rep = NamedSharding(mesh, PartitionSpec())

    def sds(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                           sharding=rep), tree)

    def st(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    from ..obs import perf

    costs = perf.get_program_costs()
    out = {}
    for bucket in normalize_buckets(buckets):
        p, s = bucket
        prog_key = bucket_program_key(params, bucket, max_batch,
                                      compute_dtype)
        with config_context(pallas_interpret=False):
            # derive the slab structs from init_kv_slab itself (the one
            # source of truth for the layout) instead of re-deriving
            # d/dh/kvh by hand — a layout change there cannot silently
            # diverge from what this tool sizes
            caches = sds(jax.eval_shape(
                lambda pp: init_kv_slab(pp, max_batch, p + s, heads,
                                        compute_dtype), params))
            tokens = st((max_batch, p + s))
            pre = _lm_prefill_slot_jit.trace(
                sds(params), caches, tokens, st(()), st((p,)), st(()),
                st((), jnp.uint32), st((), jnp.float32),
                st((), jnp.float32), st(()), heads=heads, max_len=p + s,
                compute_dtype=compute_dtype, moe=moe).lower().compile()
            dec = _lm_decode_rows_jit.trace(
                sds(params), caches, tokens, st((max_batch,)),
                st((max_batch,)), st((max_batch,), jnp.uint32),
                st((max_batch,), jnp.float32),
                st((max_batch,), jnp.float32), st((max_batch,)),
                heads=heads, max_len=p + s, compute_dtype=compute_dtype,
                moe=moe).lower().compile()
            # the compiled objects carry BOTH analyses — richest
            # capture the registry gets (memory_analysis included)
            costs.capture("lm_prefill_slot", prog_key, compiled=pre)
            costs.capture("lm_decode_rows", prog_key, compiled=dec)
            out[bucket] = max(_peak_bytes(pre.memory_analysis()),
                              _peak_bytes(dec.memory_analysis()))
            msg = planner_ratio_warning(
                bucket, out[bucket],
                bucket_kv_bytes(params, heads, bucket, compute_dtype))
            if msg is not None:
                import warnings

                warnings.warn(msg, RuntimeWarning, stacklevel=2)
    return out
