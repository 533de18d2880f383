"""The continuous-batching serving engine: one worker thread, compiled decode.

:class:`ServeEngine` is the front half of an inference stack over the
library's compiled decode programs: concurrent callers ``submit`` requests;
an admission gate (queue depth + in-flight KV-cache HBM budget, request.py)
rejects overload with a reason; a batch former (batcher.py) buckets prompts
onto a small static shape set so compiles stay bounded; and a single worker
thread keeps the device fed. Scheduling is row-level: the unit is the
slot-step, not the batch.

**The KV cache is paged**: ONE device-resident page slab
(:mod:`.kvpool` over :func:`~marlin_tpu.models.transformer.init_kv_pages`)
shared by every bucket; each row holds a host-side block table of pages.
Admission charges the request's ACTUAL pages
(:func:`~marlin_tpu.models.planner.request_pages` — a short request in a
long bucket does not reserve the bucket's worst case), completed full
prompt pages are prefix-shared copy-on-write across requests (a common
system prompt is prefilled once — :class:`~.kvpool.PagedKVPool`), and long
prompts prefill in bounded ``serve_prefill_chunk``-token chunks. The worker
lands a result only after it has dispatched the work that follows it (a
pipeline one call deep: the chip always holds the next program). Every
worker iteration:

    refill freed rows from the queue (page allocation + prefix match —
    host-side, cheap)  →  dispatch at most ``serve_prefill_chunk`` prompt
    TOKENS of prefill, oldest row first, in page-aligned chunks (several
    short rows may share the budget; a long prompt takes one chunk and
    resumes next iteration; a row's final chunk makes it decode-ready, its
    first token still on the device)  →  retire rows that expired  →
    dispatch ONE decode step over the live rows of EVERY bucket, packed
    into one call of the one decode program and fed from the device with
    the tokens the host has not seen  →  land the PREVIOUS decode call
    (announce its tokens, retire rows that emitted ``eos`` or hit their
    step budget), then the first tokens of this iteration's final chunks —
    real TTFT  →  repeat

so one long prompt can never monopolize an iteration — decode steps
interleave between its chunks, bounding TTFT for everyone else. Rows are
kept per bucket (admission, pages, chunked prefill), but a decode step
reads the weights once: the live rows of all buckets share a call of
``max_batch`` rows over the widest bucket's table (more calls of the same
program only where more than ``max_batch`` rows are live). One compiled
chunked-prefill program per bucket, plus one decode program and one
pool-wide page-copy program, for ANY per-row mix of sampling knobs.

The invariants: exactly one Result per request, per-row greedy output
bit-identical to :func:`~marlin_tpu.models.transformer.lm_generate` on the
unpadded prompt (the gather decode literally reuses ``_decode_step``), and
sampled rows on composition-independent ``fold_in(key(seed), step)``
streams.

**Pluggable programs** (serving/programs/): LM decode is one
:class:`~.programs.BucketProgram` among several — ``ServeEngine(...,
programs=[ALSScoreProgram(model), ...])`` registers additional request
types (``Request.program``) that ride the SAME spine: admission prices
each program in its own resource-unit bytes against the one HBM budget,
the former buckets program requests under ``(name, *bucket)`` keys next to
LM's ``(prompt, steps)`` tuples, and the worker loop interleaves one-shot
program batches (:class:`~.programs.ProgramRowSet` rows, a single compiled
step per bucket) between LM prefill chunks and decode steps. Every
program's rows are drained, closed, crash-recovered, frozen and adopted by
the same code paths as LM rows — a program row just has no KV pages to
carry, so migration moves it through the queued/fallback lanes.

Lifecycle: ``drain()`` stops admission and completes everything already
accepted; ``close()`` stops admission, finishes the work in flight (live
and mid-prefill rows), and retires everything still queued with a clean
``shutting_down`` Result. Both are terminal and idempotent; the worker
thread (named ``marlin-serve-*`` — the conftest leak fixture watches the
prefix) is joined before either returns. Chaos hooks (utils/faults.py):
``serve.enqueue`` fires in ``submit``; ``serve.prefill`` fires before each
prefill CHUNK — a fault fails/retries that one request and the pool stays
consistent (the chunk cursor makes prefill resumable, so a retry re-runs
the prompt from its shared prefix); ``serve.decode_step`` fires before
each decode call — a fault fails/retries only the rows that call carried. The
engine keeps serving after any of them; if a failed donated call consumed
the page slab, every resident row fails/retries and the pool is rebuilt
zeroed — the same contract worker-crash recovery gives it (supervisor.py:
pools dropped, live rows requeued, page-unit admission reservations
carried across attempts).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
import typing
import weakref

import numpy as np

from ..config import get_config
from ..models.hybrid import ModelSpec
from ..obs import memledger, perf, trace as obs_trace
from ..obs.collectors import compile_count as _compile_count, startup_span
from ..obs.exposition import (register_health_provider,
                              register_kvpool_provider,
                              register_slo_provider,
                              unregister_health_provider,
                              unregister_kvpool_provider,
                              unregister_slo_provider)
from ..obs.metrics import get_registry
from ..utils import faults
from ..utils.tracing import annotate
from .batcher import BatchFormer, normalize_buckets
from .kvpool import (PagedGroup, PagedKVPool, PagePoolExhausted,
                     auto_num_pages, auto_window_pages, decode_inputs,
                     decode_pages, group_chunk, warmup_paged)
from .metrics import ServeMetrics
from .programs import PagedLMProgram, ProgramRowSet
from .request import (SHED_REASON_PREFIX, STATUS_ERROR, STATUS_EXPIRED,
                      STATUS_OK, STATUS_REJECTED, STATUS_SHUTTING_DOWN,
                      AdmissionQueue, Request, Result, ResultHandle)

__all__ = ["ServeEngine", "MigrationError"]


def _program_result(out) -> tuple:
    """``(pages, tokens, expert counts or None)`` of a paged program's
    result: the dense block's programs return the first two, a ModelSpec's
    ``(pages, tokens, counts, logits)``."""
    return out[0], out[1], out[2] if len(out) > 2 else None


def _bucket_tag(bucket) -> str:
    """A bucket as a span field: ``256x128`` (``als x 8`` joins alike)."""
    return "x".join(str(b) for b in bucket)


def _call_tag(call) -> str:
    """The buckets one decode call carries (a list of ``(group, slots)``),
    as a span field: ``256x128+768x256`` (the profiler ends a field's value
    at a comma)."""
    return "+".join(_bucket_tag(group.bucket) for group, _ in call)


def _call_rows(call) -> list:
    """``(group, slot)`` of every row a decode call carries, in the call's
    row order."""
    return [(group, i) for group, slots in call for i in slots]


class _Launch(typing.NamedTuple):
    """A decode call the device has and the host has not landed: the rows
    it carried as ``(group, slot, entry)`` (the landing matches by ENTRY: a
    slot may have been retired and refilled meanwhile), its tokens and
    expert counts still on the device, and the step-record accounting of
    the step it belongs to (``steps`` shared by the step's calls, ``due``
    the buckets announced at this call's landing). ``seq`` is its place
    among the programs dispatched (:meth:`_Pipeline.dispatched`);
    ``leaving`` holds, by index into ``rows``, the rows that have left
    their slots with this call in flight (:class:`_Leaving`); ``sparse``,
    for a model with sparse-attention layers, what :meth:`ServeEngine
    ._sparse_blocks` counted of the call's rows (else None)."""

    rows: list
    tag: str
    t0: float
    nxt: object
    counts: object
    steps: dict
    due: list
    seq: int
    leaving: dict
    sparse: tuple | None = None


class _First(typing.NamedTuple):
    """A final prefill chunk whose first token has not landed."""

    group: PagedGroup
    slot: int
    entry: "_Entry"
    first: object
    counts: object
    t0: float
    chunk: list
    seq: int


class _Leaving(typing.NamedTuple):
    """A row that has left its slot: what its Result is built from. A row
    whose step budget ends with the decode call in flight leaves as soon
    as that call is all it waits for, so the slot and the pages are free
    for the next claim (every later program is behind the call in the
    device's stream); the call's landing appends the last token to
    ``emitted`` and answers the request."""

    entry: "_Entry"
    emitted: list
    metrics: dict  # all of the Result's but ``total_s``


class _Pipeline:
    """What one worker generation has dispatched and not landed. The loop
    is a pipeline ONE call deep: a decode call lands only after the call
    that follows it has been dispatched, so the device always holds the
    next program while the host packs, lands and retires.

    ``call`` is the decode call in flight, ``firsts`` the final chunks
    whose first token is still on the device. ``feed`` is the device vector
    (``max_batch`` tokens) the next decode call is fed from: the last
    call's ``next_tokens``, with the first tokens of final chunks written
    over the entries in ``free`` (those no continuing row reads). Every
    decode dispatch replaces it and bumps ``serial``; a row knows its
    entry by ``(fed_serial, fed_index)``, so an entry of a replaced feed is
    never read (by then the row's token has landed: depth is one).

    ``seq`` numbers the programs dispatched (prefill chunks and decode
    calls, in the device's order) and ``landed_seq`` is the last of them
    the host has seen end: a landing's interval (``landed_t`` to now) is
    one program's wall time only where the program before it had landed
    (:meth:`landed`)."""

    def __init__(self, width: int):
        self.width = width
        self.call: _Launch | None = None
        self.landing: _Launch | None = None  # the call being landed
        self.firsts: list[_First] = []
        self.landed_t = 0.0  # perf_counter at the last landing of either
        self.seq = self.landed_seq = 0
        self.serial = 0
        self.reset_feed()

    def busy(self) -> bool:
        return self.call is not None or bool(self.firsts)

    def leaving(self) -> list:
        """The entries that have left their slots and await their last
        token: in no group, so whoever gathers a generation's rows (the
        crash handler, a recovery) asks here too. Safe from another
        thread: a snapshot."""
        return [left.entry for call in (self.landing, self.call)
                if call is not None for left in list(call.leaving.values())]

    def dispatched(self) -> int:
        self.seq += 1
        return self.seq

    def landed(self, seq: int) -> bool:
        """Program ``seq`` has ended. True where the one before it had been
        seen to end: the interval since the last landing is then this
        program's alone (a chunk that is not final is never landed, so its
        device time lies in the next landing's interval)."""
        alone = self.landed_seq >= seq - 1
        self.landed_seq = max(self.landed_seq, seq)
        return alone

    def reset_feed(self) -> None:
        """Forget the device's tokens (a rebuilt pool: the old stream's
        arrays may be poisoned, and every row that read them is gone)."""
        self.feed = np.zeros(self.width, np.int32)
        self.serial += 1
        self.free = list(range(self.width))


class MigrationError(RuntimeError):
    """A freeze/adopt handoff could not run (a model with window layers,
    the wrong lifecycle state, or the target worker did not service the
    request in time). The
    router falls back to the PR 7 retry path on it."""

_engine_ids = itertools.count()
_mig_tokens = itertools.count()  # distinct migration-blob ledger names

# real-seconds cap on one condition wait under an INJECTED clock: bounds how
# stale the worker's view of a fake clock can get (tests advance it between
# polls). Real-clock engines never poll — they wait on the condition until
# notified or the exact max_wait hint elapses.
_POLL_CAP_S = 0.02


class _Entry:
    """One admitted request riding through the former to a row.
    ``queue_s`` is stamped when the scheduler claims the entry. ``trace``
    is the request's span context (obs/trace.py), captured at submit and
    re-activated by the worker thread around every record the request
    produces — that cross-thread handoff is what joins one request's
    enqueue/prefill/result records into one trace in the JSONL.

    ``attempt`` counts executions of this request (1-based); a retry
    re-queues a FRESH entry via :meth:`retry` — same request, handle,
    admission cost (the HBM reservation is carried, never re-charged), and
    original ``enq_t`` (latency is honest: it includes the failed
    attempts) — and marks this one ``superseded`` so a stale worker
    generation that still holds it can never retire it. The exactly-once
    Result is enforced twice over: superseded entries no-op in ``_retire``,
    and the admission budget is released only by whoever wins the handle's
    single ``_set``."""

    __slots__ = ("request", "handle", "bucket", "cost", "enq_t", "queue_s",
                 "trace", "attempt", "superseded")

    def __init__(self, request, handle, bucket, cost, enq_t, trace=None,
                 attempt=1):
        self.request = request
        self.handle = handle
        self.bucket = bucket
        self.cost = cost
        self.enq_t = enq_t
        self.queue_s = None
        self.trace = trace
        self.attempt = attempt
        self.superseded = False

    def retry(self) -> "_Entry":
        """The next-attempt twin (this entry becomes superseded)."""
        self.superseded = True
        return _Entry(self.request, self.handle, self.bucket, self.cost,
                      self.enq_t, trace=self.trace, attempt=self.attempt + 1)

    def attempts_left(self) -> bool:
        return self.attempt < self.request.max_attempts


class ServeEngine:
    """Continuous-batching inference engine over a trained LM.

    ``params``/``heads``/``compute_dtype``/``moe`` describe the model exactly
    as :func:`lm_generate_batch` takes them. Knobs default from the global
    config: ``buckets`` (``serve_buckets``), ``max_batch``
    (``serve_max_batch``), ``max_wait_ms`` (``serve_max_wait_ms``),
    ``queue_depth`` (``serve_queue_depth``); ``hbm_budget_bytes`` defaults to
    the planner's :func:`~marlin_tpu.models.planner.usable_hbm_bytes` (0
    disables the byte gate). ``clock`` is the engine's *policy* clock
    (deadlines, max_wait, latency metrics) — injectable for deterministic
    tests; wall throughput is always measured on the real clock. ``log``
    overrides the default EventLog for ``serve`` records.

    The KV cache is the paged pool (block tables over one shared page
    slab, prefix caching, chunked prefill); ``page_len``/``num_pages``/
    ``prefill_chunk``/``prefix_cache``/``decode_kernel`` override the
    ``serve_*`` knobs. A model with recurrent state (a
    :class:`~marlin_tpu.models.hybrid.ModelSpec` with ``has_state``) keeps
    the prefix cache OFF unless ``prefix_cache=True`` is asked for: it then
    shares a prefix only up to a boundary whose state it kept a snapshot
    of, in ``snapshot_slots`` further state slots (default: a row's worth,
    ``len(buckets) * max_batch``; ``serving/kvpool.py``).

    ``programs`` registers additional :class:`~.programs.BucketProgram`
    instances (ALS scoring, PageRank queries, classification, ...) served
    next to LM traffic — requests route by ``Request.program``.

    Usable as a context manager (``close()`` on exit); ``start=False`` defers
    the worker thread so tests can stage a queue before any dispatch."""

    @startup_span("serve.engine.init")
    def __init__(self, params: dict, heads, *, buckets=None,
                 max_batch: int | None = None,
                 max_wait_ms: float | None = None,
                 queue_depth: int | None = None,
                 hbm_budget_bytes: int | None = None,
                 compute_dtype: str | None = None, moe: tuple | None = None,
                 page_len: int | None = None, num_pages: int | None = None,
                 prefill_chunk: int | None = None,
                 prefix_cache: bool | None = None,
                 decode_kernel: str | None = None,
                 window_pages: int | None = None,
                 state_slots: int | None = None,
                 snapshot_slots: int | None = None,
                 programs=None,
                 clock=time.monotonic, log=None, start: bool = True):
        cfg = get_config()
        self.params = params
        self.heads = heads
        #: the model's description where ``heads`` is one
        #: (:class:`~marlin_tpu.models.hybrid.ModelSpec`), else None: the
        #: dense block that an integer head count names
        self._spec = heads if isinstance(heads, ModelSpec) else None
        if self._spec is not None:
            if moe is not None:
                raise ValueError("`moe` sets the routing of the dense "
                                 "block's GShard layer; a ModelSpec carries "
                                 "its own")
            if compute_dtype is None:
                compute_dtype = self._spec.compute_dtype
        self.compute_dtype = compute_dtype
        self.moe = moe
        self.buckets = normalize_buckets(
            cfg.serve_buckets if buckets is None else buckets)
        self.max_batch = int(cfg.serve_max_batch if max_batch is None
                             else max_batch)
        wait_ms = cfg.serve_max_wait_ms if max_wait_ms is None else max_wait_ms
        depth = int(cfg.serve_queue_depth if queue_depth is None
                    else queue_depth)
        # --- paged-pool geometry (serving/kvpool.py) -----------------------
        # decode-attention backend, resolved once ('auto' → pallas on TPU,
        # gather elsewhere) so every program key / warmup / dispatch in
        # this engine agrees on it
        from ..models.transformer import resolve_decode_kernel

        self._decode_kernel = resolve_decode_kernel(
            cfg.serve_decode_kernel if decode_kernel is None
            else decode_kernel)
        self._page_len = int(cfg.serve_page_len if page_len is None
                             else page_len)
        if self._decode_kernel == "pallas":
            # the fused kernel streams whole pages as sublane-aligned
            # blocks; round the page size up rather than fall back
            from ..ops.paged_attention import align_page_len

            self._page_len = align_page_len(self._page_len)
        #: the one decode program has the widest bucket's shape: this is its
        #: table's width
        self._decode_pages = decode_pages(self.buckets, self._page_len)
        self._prefill_chunk = int(cfg.serve_prefill_chunk
                                  if prefill_chunk is None else prefill_chunk)
        self._prefix_cache = bool(cfg.serve_prefix_cache
                                  if prefix_cache is None else prefix_cache)
        npages = int(cfg.serve_num_pages if num_pages is None else num_pages)
        if npages <= 0:
            npages = auto_num_pages(self.buckets, self.max_batch,
                                    self._page_len)
        self._num_pages = npages
        self._kvpool: PagedKVPool | None = None  # built lazily / on warmup
        #: the window class (a ModelSpec with sliding layers): the ring of
        #: pages a row holds there, the class's size, one page's bytes.
        #: ``_ring`` is None for the dense block, 0 for a spec without a
        #: sliding layer
        self._ring = None if self._spec is None else 0
        self._window_pages = 0
        self._window_page_bytes = 0
        from ..models.planner import kv_page_bytes

        self._page_bytes = kv_page_bytes(params, heads, self._page_len,
                                         compute_dtype)
        if self._spec is not None and self._spec.has_window:
            from ..models.hybrid import window_ring_pages

            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True with a model that has "
                    "sliding-window layers: sharing a window layer's "
                    "pages is not built; leave it unset (off)")
            self._prefix_cache = False
            self._ring = window_ring_pages(
                self._spec.window,
                max(group_chunk(b, self._page_len, self._prefill_chunk)
                    for b in self.buckets), self._page_len)
            self._window_pages = int(window_pages or auto_window_pages(
                self.buckets, self.max_batch, self._ring))
            self._window_page_bytes = kv_page_bytes(
                params, heads, self._page_len, compute_dtype,
                kind="sliding")
        #: the recurrent-state slots (a ModelSpec with recurrent mixers):
        #: how many the pool holds for rows, the dummy slot 0 among them
        #: (default: one for every row of every bucket), one slot's bytes
        #: over all layers, charged at admission beside the row's pages,
        #: and the snapshot slots after them (0: no prefix is shared)
        self._state_slots = 0
        self._state_slot_bytes = 0
        self._snapshot_slots = 0
        if self._spec is not None and self._spec.has_state:
            #: the prefill dispatch span's field for the tokens a chunk's
            #: mixers scan, named by the mixer's kind
            self._mixer_tokens = (
                "ssm_tokens" if self._spec.ssm is not None else
                "delta_tokens" if self._spec.delta is not None else
                "kda_tokens" if self._spec.kda is not None else
                "lightning_tokens" if self._spec.lightning is not None else
                "conv_tokens")
            # a shared page is no use without the recurrent state at the
            # prefix's end: sharing is opt-in, and rests on snapshots
            self._prefix_cache = bool(prefix_cache)
            if self._prefix_cache:
                self._snapshot_slots = int(
                    snapshot_slots or len(self.buckets) * self.max_batch)
            chunk = self._spec.mixer.chunk
            for b in self.buckets:
                width = group_chunk(b, self._page_len, self._prefill_chunk)
                if width % min(chunk, width):
                    raise ValueError(
                        f"bucket {b}'s prefill chunk of {width} tokens is "
                        f"not whole blocks of the chunked scan ({chunk}: "
                        f"mamba_chunk_size / linear_chunk_size / "
                        f"kda_chunk_size)")
            self._state_slots = int(
                state_slots or 1 + len(self.buckets) * self.max_batch)
            self._state_slot_bytes = self._spec.state_slot_bytes(
                compute_dtype)
        if hbm_budget_bytes is None:
            from ..models.planner import usable_hbm_bytes

            hbm_budget_bytes = usable_hbm_bytes()
        self._clock = clock
        self._real_clock = clock is time.monotonic
        self.metrics = ServeMetrics(log=log)
        self._queue = AdmissionQueue(depth, hbm_budget_bytes)
        self._cond = threading.Condition()
        self._former = BatchFormer(self.buckets, self.max_batch,
                                   max_wait=float(wait_ms) / 1e3)
        # Request.program routing table: LM (this engine's paged path,
        # wrapped as the first BucketProgram) plus whatever the caller
        # registered. Former/pool keys for non-LM buckets are namespaced
        # (name, *bucket) tuples — a str head can never collide with LM's
        # (prompt, steps) int pairs
        self._programs: dict[str, object] = {"lm": PagedLMProgram(self)}
        for p in (programs or ()):
            if not getattr(p, "name", ""):
                raise ValueError(f"program {p!r} must set a non-empty .name")
            if p.name in self._programs:
                raise ValueError(f"duplicate program name {p.name!r}")
            self._programs[p.name] = p
        # running | draining | freezing | frozen | closing | closed —
        # freezing/frozen are the migration pause (freeze_rows): the worker
        # parks leaving its pools intact and the freezing thread takes over
        self._state = "running"
        #: worker mailbox for cross-engine migration ops (adopt_rows /
        #: export_prefixes / import_prefixes): (kind, payload, event, box)
        #: tuples serviced at the top of each worker iteration — the pool
        #: stays single-threaded, the requester waits on the event
        self._mig_inbox: collections.deque = collections.deque()
        self._started = False
        #: True while warmup() compiles bucket programs on the caller's
        #: thread — the supervisor's watchdog skips the stuck check (first
        #: compiles routinely outlast any sane watchdog_s; crash detection
        #: stays on), so a freshly scaled-out replica is never "recovered"
        #: mid-warmup
        self._warming = False
        eid = next(_engine_ids)
        self._name = f"marlin-serve-{eid}"
        # --- supervised recovery (serving/supervisor.py) -------------------
        # the worker generation: a recovery bumps it, spawns a fresh thread,
        # and any stale worker still unwinding exits at its next gen check
        # without touching shared state (its entries are superseded)
        self._gen = 0
        self._pools: dict[tuple, object] = {}   # current worker's slot pools
        self._pipe: _Pipeline | None = None     # ... and what it has in flight
        self._claimed: list = []                # claimed-but-unslotted rows
        self._crash: tuple | None = None        # (exc, undone entries)
        self._on_crash = None                   # supervisor's prompt-wake cb
        self._abandoned = None                  # superseded wedged thread:
        # never joined (breaker opened on a stuck worker — close() must not
        # block on a thread that may never return from its device call)
        self._idle = False                      # worker parked in cond.wait
        # EWMA of per-request service seconds (ok results, engine clock) —
        # the deadline-admission estimate's only input
        self._service_ewma = 0.0
        self._thread = self._make_thread(0)
        # --- the flight recorder (obs/perf.py) -----------------------------
        # the step-time black box: per-iteration records from the worker
        # loop, dumped on worker faults, on close, and via GET /debug/flight
        self.flight = perf.FlightRecorder(name=self._name)
        self._heartbeat: float | None = None  # real clock; worker stamps it
        self._live_rows = 0                   # worker-written, healthz-read
        self._finalized = False
        # readiness: /healthz reports this engine's lifecycle and 503s once
        # it leaves "accepting" (weakref — the provider must never pin a
        # dead engine; terminal close/drain unregister explicitly)
        ref = weakref.ref(self)
        name = self._name

        def _health():
            eng = ref()
            if eng is None:
                # abandoned without close(): drop out silently — a dead
                # entry must not 503 an otherwise healthy process for one
                # probe (health_payload skips None)
                unregister_health_provider(name)
                return None
            return eng._health_info()

        register_health_provider(name, _health)

        def _kvpool_report():
            eng = ref()
            if eng is None:
                unregister_kvpool_provider(name)
                return None
            return eng.kvpool_audit()

        register_kvpool_provider(name, _kvpool_report)
        # --- serving SLOs (obs/slo.py + obs/timeseries.py) -----------------
        # built only when objectives are configured (serve_slo) — otherwise
        # the hot path carries literally nothing (one None check per worker
        # iteration). The store and the SLO engine run on THIS engine's
        # injected clock; evaluation is scrape- and worker-driven (tick is
        # rate-limited), never a new thread.
        self._slo = None
        self._ts = None
        self._ts_collector = None
        if cfg.serve_slo:
            from ..obs.slo import SloEngine, objectives_from_config
            from ..obs.timeseries import TimeSeriesStore, install_collector

            self._ts = TimeSeriesStore(
                window_s=float(cfg.serve_ts_window_s),
                bucket_s=float(cfg.serve_ts_bucket_s), clock=clock)
            self.metrics.attach_timeseries(self._ts)
            self._slo = SloEngine(objectives_from_config(cfg), self._ts,
                                  scope=self._name, log=log, clock=clock)
            # scrape-driven pump, restricted to the objectives' families:
            # the registry is process-global (a labeled child per engine
            # ever created) while the store is a bounded per-engine ring —
            # an unfiltered pump would exhaust max_series in a long-lived
            # process and starve the latency-sample feed
            self._ts_collector = install_collector(
                self._ts, only=self._slo.pump_families)
            if cfg.serve_slo_shed:
                # graceful degradation: a breach arms admission shedding at
                # level = number of breached objectives (deeper breach ->
                # higher priority tiers shed); clear disarms. In-flight
                # work is never touched (request.py AdmissionQueue).
                slack = float(cfg.serve_slo_shed_slack_s)

                def _on_breach(ev, _q=self._queue, _slack=slack):
                    breached = ev.get("breached") or ()
                    if breached:
                        _q.set_shed(len(breached),
                                    reason=",".join(breached),
                                    protect_slack_s=_slack)
                    else:
                        _q.clear_shed()

                self._slo.add_breach_hook(_on_breach)

            def _slo_report():
                eng = ref()
                if eng is None:
                    unregister_slo_provider(name)
                    return None
                return eng._slo_payload()

            register_slo_provider(name, _slo_report)
        if start:
            self.start()

    # ------------------------------------------------------------- lifecycle

    def _make_thread(self, gen: int) -> threading.Thread:
        """A worker thread for one generation. Restarted generations keep
        the ``marlin-serve`` prefix (the conftest leak fixture and the
        flight recorder key on it) with a ``-r<gen>`` suffix."""
        name = self._name if gen == 0 else f"{self._name}-r{gen}"
        return threading.Thread(target=self._run_paged, args=(gen,),
                                daemon=True, name=name)

    def start(self) -> None:
        """Start the worker thread (idempotent; no-op once shutting down)."""
        with self._cond:
            if self._started or self._state != "running":
                return
            self._started = True
        self._thread.start()

    def warmup(self) -> int:
        """Compile the engine's programs before traffic: every bucket's
        chunked prefill, the one decode program and the shared page-copy
        program (kvpool.warmup_paged, against THIS engine's pool — program
        identity includes the slab shape). Call before the first submit — warmup
        drives the live pool."""
        self._warming = True
        try:
            with startup_span("serve.warmup", buckets=len(self.buckets)):
                with self._cond:  # never race a worker's lazy pool creation
                    pool = self._ensure_kvpool()
                n = warmup_paged(self.params, self.heads, self.buckets,
                                 self.max_batch, pool, self._prefill_chunk,
                                 self.compute_dtype, self.moe,
                                 kernel=self._decode_kernel)
                for name, prog in self._programs.items():
                    if name != "lm":  # LM compiled above against the live pool
                        n += prog.warmup()
                return n
        finally:
            self._warming = False

    def swap_model(self, program: str, model) -> None:
        """Atomically install new weights on a resident BucketProgram (the
        hot-update seam: same shapes keep the compiled programs serving —
        the swap is an operand change, never a recompile). Raises for an
        unknown program or one without a ``swap_model`` hook; on success
        records one ``ev="swap"`` event +
        ``marlin_serve_program_swaps_total{program}``."""
        prog = self._programs.get(program)
        if prog is None:
            raise ValueError(
                f"unknown program {program!r} (this engine serves "
                f"{sorted(self._programs)})")
        hook = getattr(prog, "swap_model", None)
        if hook is None:
            raise ValueError(
                f"program {program!r} has no swap_model hook")
        hook(model)
        self.metrics.record_swap(program)

    def pending(self) -> int:
        """Requests admitted but not yet retired (queued + in flight)."""
        return self._queue.count

    # ------------------------------------------------------- introspection

    def _health_info(self) -> dict:
        """The /healthz readiness payload for this engine: lifecycle state
        (``accepting`` while running), live slot rows, queue depth, and the
        worker heartbeat age (None until the worker's first iteration).
        Lock-free reads of GIL-atomic fields — the probe must never contend
        with the worker."""
        state = {"running": "accepting", "draining": "draining",
                 "freezing": "draining", "frozen": "draining",
                 "closing": "closed", "closed": "closed"}[self._state]
        hb = self._heartbeat
        return {
            "state": state,
            "live_slots": self._live_rows,
            "queue_depth": self._queue.count,
            "worker_started": self._started,
            "heartbeat_age_s": (round(time.monotonic() - hb, 3)
                                if hb is not None else None),
        }

    def _slo_payload(self) -> dict | None:
        """The ``GET /debug/slo`` scope payload for this engine: the SLO
        engine's evaluation (ticked on the probe, so a scrape always sees
        a fresh-enough verdict without any poller thread) plus the health
        block and paged-pool gauges the ops console renders as topology.
        None when no objectives are configured (the provider prunes)."""
        slo = self._slo
        if slo is None:
            return None
        try:
            slo.tick(self._clock())
            p = slo.payload()
        except Exception:  # pragma: no cover - probe must never 500
            return None
        p["health"] = self._health_info()
        m = self.metrics
        p["pages"] = {"total": m.pages_total, "used": m.pages_used,
                      "shared": m.pages_shared}
        p["shed_level"] = self._queue.shed_level
        p["shed_count"] = self._queue.shed_count
        return p

    def _ensure_kvpool(self) -> PagedKVPool:
        """The engine's one paged pool, built lazily (warmup or the first
        admission) and rebuilt zeroed after a recovery or slab loss."""
        pool = self._kvpool
        if pool is None:
            with startup_span("serve.kvpool.init",
                              pages_total=self._num_pages,
                              state_slots=self._state_slots):
                # analyze: single-writer — the pool pointer belongs to the
                # live scheduler generation; _recover/close swap it only
                # after the worker they superseded has stopped dispatching
                pool = self._kvpool = PagedKVPool(
                    self.params, self.heads, self._num_pages, self._page_len,
                    self.compute_dtype, self._prefix_cache,
                    window_pages=self._window_pages, ring=self._ring or 0,
                    state_slots=self._state_slots,
                    snapshot_slots=self._snapshot_slots)
            self.metrics.record_pages(pool.capacity, 0, 0)
            # account the slab in the process memory ledger: the free rides
            # every drop path (recovery, slab loss, terminal close), so a
            # rebuild re-registers the same name without double-counting
            led = memledger.get_ledger()
            led.free(f"kvpool:{self._name}", strict=False)
            led.register(f"kvpool:{self._name}",
                         self._num_pages * self._page_bytes
                         + self._window_pages * self._window_page_bytes
                         + (self._state_slots + self._snapshot_slots)
                         * self._state_slot_bytes,
                         "kvpool", owner=self._name)
        return pool

    def _new_group(self, bucket) -> PagedGroup:
        """A bucket's row bookkeeping, shaped for this engine's programs."""
        return PagedGroup(bucket, self.max_batch, self._page_len,
                          self._prefill_chunk, ring=self._ring,
                          stateful=bool(self._state_slots))

    def _record_pages(self, pool) -> None:
        st = pool.stats()
        self.metrics.record_pages(st["total"], st["used"], st["shared"])

    def _flight_dump(self, reason: str) -> None:
        """Dump the flight ring (never raises — rides failure paths)."""
        try:
            self.flight.dump(reason=reason)
        except Exception:
            pass

    def _finalize_obs(self) -> None:
        """Terminal observability flush (close/drain, idempotent): dump the
        flight ring, then drop out of the /healthz registry — a terminated
        engine must not hold the process at 503."""
        if self._finalized:
            return
        self._finalized = True
        self._flight_dump("close")
        # a terminated engine must leave the memory ledger clean: sweep
        # everything it still owns (the KV slab, unconsumed migration
        # blobs) and land one attribution snapshot for the post-hoc report
        try:
            memledger.get_ledger().free_owner(self._name)
            memledger.emit_snapshot()
        except Exception:
            pass
        unregister_health_provider(self._name)
        unregister_kvpool_provider(self._name)
        unregister_slo_provider(self._name)
        if self._ts_collector is not None:
            get_registry().remove_collector(self._ts_collector)
            self._ts_collector = None
        self.metrics.attach_timeseries(None)

    def _join_worker(self) -> None:
        """Join until no worker generation will run again — a supervisor
        may swap in a fresh generation mid-join (crash during drain), or be
        a poll interval away from consuming a crash stash; returning after
        joining a dead predecessor would declare the engine closed with
        work still queued. Terminates because recovery is bounded: the
        supervisor's breaker (or the absence of a supervisor) guarantees a
        final generation."""
        if not self._started:
            return
        waited = 0.0
        while True:
            t = self._thread
            if t is self._abandoned:
                return  # a wedged generation the breaker gave up on: it
                # may never return from its device call, and everything it
                # held was already retired — joining would hang shutdown
            try:
                t.join()
            except RuntimeError:
                # a recovery publishes the fresh generation's thread under
                # the lock but starts it only after releasing it; joining
                # inside that window raises "cannot join thread before it
                # is started" — yield and re-join once the starter runs
                time.sleep(0.001)
                continue
            with self._cond:
                if self._thread is not t:
                    waited = 0.0
                    continue  # a recovery swapped in a new generation
                # stash pending + supervisor attached + a state it still
                # recovers in (check() skips closing/closed engines, so
                # waiting there would deadlock close())
                if (self._on_crash is not None and self._crash is not None
                        and self._state in ("running", "draining")):
                    recovery_pending = True  # stashed, not yet respawned
                else:
                    return
            if recovery_pending:
                if waited >= 5.0:
                    # an attached supervisor whose monitor never consumed
                    # the stash (e.g. Supervisor(start=False)): waiting
                    # forever would hang shutdown — return and let the
                    # caller's _fail_crash_stash / leftover paths resolve
                    # everything the dead worker held
                    return
                time.sleep(0.005)  # let the supervisor consume the stash
                waited += 0.005

    def _fail_crash_stash(self, reason: str) -> None:
        """Retire whatever a crashed, never-recovered worker was holding
        (drain/close with no supervisor attached, or a breaker-opened
        engine) — the shutdown path must strand nothing."""
        with self._cond:
            crash = self._crash
            self._crash = None
        if crash is None:
            return
        for e in crash[1]:
            if not e.handle.done():
                self._retire(e, Result(e.request.rid, STATUS_ERROR,
                                       reason=reason))

    def drain(self) -> None:
        """Graceful stop: no new admissions (post-drain submits resolve
        ``shutting_down``), but everything already accepted — queued and in
        flight — completes. Partial batches dispatch immediately. Terminal:
        the worker exits and is joined before this returns."""
        self._queue.close("engine draining (no new admissions)")
        self.start()  # a never-started engine still owes queued results
        with self._cond:
            if self._state == "running":
                self._state = "draining"
            self._cond.notify_all()
        self._join_worker()
        self._fail_mig_inbox("engine drained before servicing migration")
        self._fail_crash_stash("serving worker died while draining")
        with self._cond:
            self._state = "closed"
            leftovers = self._former.take_all()
        for e in leftovers:
            # only reachable when the last worker generation died with no
            # supervisor left to respawn one — queued work still resolves
            self._retire(e, Result(e.request.rid, STATUS_ERROR,
                                   reason="serving worker lost while "
                                          "draining"))
        self._finalize_obs()

    def close(self) -> None:
        """Fast stop: no new admissions, the batch in flight completes, and
        every still-queued request is retired with a clean
        ``shutting_down`` Result (never silently dropped). Idempotent."""
        self._queue.close("engine shutting down")
        with self._cond:
            if self._state == "closed":
                return
            self._state = "closing"
            leftovers = self._former.take_all()
            self._cond.notify_all()
        for e in leftovers:
            self._retire(e, Result(
                e.request.rid, STATUS_SHUTTING_DOWN,
                reason="engine closed before this request was scheduled"))
        self._join_worker()
        self._fail_mig_inbox("engine closed before servicing migration")
        self._fail_crash_stash("serving worker died; engine closed before "
                               "recovery")
        with self._cond:
            self._state = "closed"
        self._finalize_obs()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------- admission

    def submit(self, request: Request) -> ResultHandle:
        """Admit one request. Always returns a handle that will carry exactly
        one Result; overload / no-bucket / past-deadline submissions resolve
        immediately with ``rejected`` / ``expired`` status and a reason.

        Opens the request's span (a child of the caller's active span when
        there is one, else a fresh trace root), so every record the request
        ever produces — here and on the worker thread — shares one
        ``trace_id``."""
        ctx = obs_trace.child_of_current(f"serve.request.{request.rid}")
        with obs_trace.use(ctx):
            return self._submit(request, ctx)

    def _submit(self, request: Request, ctx) -> ResultHandle:
        with annotate("serve.submit", rid=request.rid) as span:
            faults.fire("serve.enqueue", path=str(request.rid))
            handle = ResultHandle(request)
            now = self._clock()
            prog = self._programs.get(request.program)
            if prog is None:
                return self._refuse(handle, STATUS_REJECTED, (
                    f"unknown program {request.program!r} (this engine serves "
                    f"{sorted(self._programs)})"))
            why = prog.validate(request)
            if why is not None:
                return self._refuse(handle, STATUS_REJECTED, why)
            pbucket = prog.pick_bucket(request)
            if pbucket is None:
                return self._refuse(handle, STATUS_REJECTED,
                                    prog.refuse_no_bucket(request))
            # former/pool key: LM keeps its bare (prompt, steps) tuple (the
            # pre-refactor keys — events, pools, and migration manifests are
            # unchanged); other programs namespace theirs under their name
            bucket = (pbucket if request.program == "lm"
                      else (prog.name,) + tuple(pbucket))
            span.set_metadata(bucket=_bucket_tag(bucket))
            # resolve the relative/default deadline to an absolute
            # engine-clock one, ONCE — a router failover or worker restart
            # must not hand the request a fresh budget
            if request.deadline is None:
                rel = request.deadline_s
                if rel is None:
                    rel = get_config().serve_default_deadline_s
                if rel is not None:
                    request.deadline = now + float(rel)
                    request.deadline_s = None
            if request.deadline is not None and request.deadline <= now:
                return self._refuse(handle, STATUS_EXPIRED, (
                    f"deadline {request.deadline} already passed at "
                    f"submission (now {now})"))
            # deadline-aware admission: with service history (EWMA of ok
            # per-request seconds), a request whose projected completion
            # behind the current queue already overshoots its deadline is
            # refused NOW — cheaper for everyone than decoding it into a
            # guaranteed expiry
            if request.deadline is not None and self._service_ewma > 0.0:
                projected = now + self._service_ewma * (
                    1.0 + self._queue.count / self.max_batch)
                if projected > request.deadline:
                    return self._refuse(handle, STATUS_REJECTED, (
                        f"deadline unmeetable: projected completion "
                        f"{projected:.3f} > deadline "
                        f"{request.deadline:.3f} at queue depth "
                        f"{self._queue.count} (service est "
                        f"{self._service_ewma:.3f}s)"))
            # the program prices its own resource units (LM: actual KV pages;
            # one-shot programs: their padded device row)
            # against the one shared HBM admission budget; a capacity refusal
            # (e.g. more pages than the pool holds) raises the reason
            try:
                cost = prog.admission_cost(request, pbucket)
            except ValueError as exc:
                return self._refuse(handle, STATUS_REJECTED, str(exc))
            reason = self._queue.try_admit(
                cost, priority=request.priority,
                deadline_slack_s=(request.deadline - now
                                  if request.deadline is not None else None))
            if reason is not None:
                # a drain/close-shut gate is a deterministic shutting_down
                # Result (the caller can failover/retry elsewhere); overload
                # stays a rejection with the backpressure reason. Matching the
                # RETURNED reason (the close reason never changes once set)
                # keeps a "queue full" verdict that raced a concurrent drain
                # labeled as the backpressure it was
                if reason == self._queue.closed_reason:
                    return self._refuse(handle, STATUS_SHUTTING_DOWN, reason)
                if (self._slo is not None
                        and reason.startswith(SHED_REASON_PREFIX)):
                    self._slo.record_shed()
                return self._refuse(handle, STATUS_REJECTED, reason)
            entry = _Entry(request, handle, bucket, cost, now, trace=ctx)
            with self._cond:
                if self._state != "running":
                    admitted = False
                else:
                    self._former.add(entry)
                    if self._idle:
                        # an IDLE worker's heartbeat is legitimately old (it
                        # blocks in cond.wait): restart the watchdog window at
                        # admission so the wakeup isn't a false positive. A
                        # busy (possibly wedged) worker is NOT idle — traffic
                        # must never keep refreshing a dead worker's pulse
                        self._heartbeat = time.monotonic()
                    self._cond.notify_all()
                    admitted = True
            if not admitted:  # raced with drain()/close(): don't strand
                self._queue.release(cost)
                return self._refuse(handle, STATUS_SHUTTING_DOWN,
                                    "engine is shutting down")
            self.metrics.record_enqueue(request.rid, bucket, self._queue.count,
                                        program=request.program)
            self.metrics.record_queue(self._queue.count,
                                      self._queue.bytes_in_flight)
            return handle

    def submit_many(self, requests) -> list[ResultHandle]:
        return [self.submit(r) for r in requests]

    def _refuse(self, handle, status: str, reason: str) -> ResultHandle:
        handle._set(Result(handle.request.rid, status, reason=reason))
        if status == STATUS_REJECTED:
            self.metrics.record_reject(handle.request.rid, reason,
                                       program=handle.request.program)
        else:
            self.metrics.record_result(handle.request.rid, status,
                                       program=handle.request.program)
        return handle

    # ----------------------------------------------------------- worker loop

    def _crash_handler(self, exc: BaseException, held: list,
                       gen: int) -> bool:
        """A worker generation is dying with ``held`` entries in hand.
        Supervised (``_on_crash`` installed, engine still serving): stash
        the undone entries for :meth:`_recover`, kick the supervisor, and
        return True — the worker exits quietly and the engine KEEPS
        accepting (requests queue up behind the restart). Unsupervised:
        the legacy contract — fail everything held plus the queued backlog
        with ``error`` Results so no submitter is ever stranded, and
        return False so the thread log still sees the exception. A
        SUPERSEDED generation dying late exits quietly without stashing —
        its entries were already requeued or failed by the recovery that
        superseded it, and a spurious stash would restart (and burn a
        retry attempt of) the healthy current generation."""
        cb = leftovers = None
        with self._cond:
            if self._gen != gen:
                return True  # stale straggler: recovery already ran
            undone = []
            seen = set()
            for e in held:
                if id(e) in seen or e.handle.done() or e.superseded:
                    continue
                seen.add(id(e))
                undone.append(e)
            # "freezing" counts as supervised even though the supervisor
            # idles there: freeze_rows() itself consumes the stash (the
            # crashed rows ride the migration fallback/retry path) — an
            # unsupervised fail-everything here would break exactly-once
            # for rows the freeze is about to hand to another replica
            supervised = ((self._on_crash is not None
                           and self._state in ("running", "draining"))
                          or self._state == "freezing")
            if supervised:
                self._crash = (exc, undone)
                cb = self._on_crash
            else:
                leftovers = self._former.take_all()
                self._state = "closing"
            self._claimed = []
        self._flight_dump("worker-died")
        if supervised:
            try:
                cb()
            except Exception:  # the supervisor's poll loop still catches it
                pass
            return True
        for e in leftovers + undone:
            if not e.handle.done():
                self._retire(e, Result(e.request.rid, STATUS_ERROR,
                                       reason="serving worker died"))
        return False

    def _retire(self, entry: _Entry, result: Result) -> None:
        if entry.superseded:
            return  # a retried twin owns this request (and its budget) now
        if entry.attempt > 1:
            result.metrics.setdefault("attempt", entry.attempt)
        try:
            entry.handle._set(result)
        except RuntimeError:
            # lost the exactly-once race to a stale worker generation's
            # twin — the winner released the budget and recorded the result
            return
        self._queue.release(entry.cost)
        if result.status == STATUS_OK:
            total = result.metrics.get("total_s")
            if total is not None:
                # EWMA of per-request SERVICE time — total minus queue wait
                # (the deadline-admission projection multiplies this by the
                # queue depth, so feeding end-to-end total_s would count
                # queueing twice and over-reject meetable deadlines, and a
                # single post-recovery straggler would poison the estimate)
                svc = max(total - (result.metrics.get("queue_s") or 0.0),
                          0.0)
                # analyze: single-writer — advisory latency estimate for
                # deadline admission; a lost EWMA update skews one sample,
                # never correctness, and taking the engine lock on the
                # retire path would order it against the submit path
                self._service_ewma = (svc if self._service_ewma == 0.0
                                      else 0.8 * self._service_ewma
                                      + 0.2 * svc)
        # re-activate the request's span on whichever thread retires it, so
        # the result record joins the request's trace
        with obs_trace.use(entry.trace):
            self.metrics.record_result(
                result.rid, result.status,
                bucket=result.metrics.get("bucket"),
                queue_s=result.metrics.get("queue_s"),
                total_s=result.metrics.get("total_s"),
                ttft_s=result.metrics.get("ttft_s"),
                attempt=entry.attempt,
                pages=result.metrics.get("pages"),
                shared_pages=result.metrics.get("shared_pages"),
                program=entry.request.program)
        self.metrics.record_queue(self._queue.count,
                                  self._queue.bytes_in_flight)

    @staticmethod
    def _is_program_bucket(bucket) -> bool:
        """True for a namespaced (name, *bucket) program key — the one
        type test that routes a former bucket to the program lane (LM
        buckets are bare (prompt, steps) int tuples)."""
        return (isinstance(bucket, tuple) and bool(bucket)
                and isinstance(bucket[0], str))

    def _claim(self, pools) -> list[_Entry]:
        """Claim queued entries for free slots, per bucket (called under the
        engine lock; prefill happens outside it). Program buckets claim up
        to their program's padded width instead of the LM max_batch."""
        claimed = []
        for bucket in self._former.pending_buckets():
            pool = pools.get(bucket)
            if pool is not None:
                free = len(pool.free_slots())
            elif self._is_program_bucket(bucket):
                prog = self._programs.get(bucket[0])
                # an unregistered program's entries (a misrouted adopt)
                # still claim: _admit_program_rows retires them cleanly
                free = prog.width if prog is not None else self.max_batch
            else:
                free = self.max_batch
            if free:
                claimed.extend(self._former.take_for_bucket(bucket, free))
        return claimed

    def _admit_program_rows(self, pools, claimed) -> None:
        """Bind claimed program entries to :class:`ProgramRowSet` slots —
        host-side only; a one-shot program's device work happens in
        :meth:`_step_program_rows`. Dispatch order matches the paged
        admit: priority first, then arrival."""
        if not claimed:
            return
        claimed = sorted(claimed,
                         key=lambda e: (-e.request.priority, e.request.rid))
        for e in claimed:
            with obs_trace.use(e.trace):
                now = self._clock()
                r = e.request
                prog = self._programs.get(e.bucket[0])
                if prog is None:
                    # a misrouted adopt: the target fleet lacks this
                    # program — resolve, never strand
                    self._retire(e, Result(
                        r.rid, STATUS_ERROR,
                        reason=f"program {e.bucket[0]!r} is not registered "
                               f"on this engine",
                        metrics={"bucket": e.bucket,
                                 "queue_s": now - e.enq_t,
                                 "total_s": now - e.enq_t}))
                    continue
                if r.deadline is not None and r.deadline <= now:
                    self._retire(e, Result(
                        r.rid, STATUS_EXPIRED,
                        reason=f"deadline {r.deadline} passed before "
                               f"dispatch (dispatched at {now})",
                        metrics={"bucket": e.bucket,
                                 "queue_s": now - e.enq_t,
                                 "total_s": now - e.enq_t}))
                    continue
                e.queue_s = now - e.enq_t
                rows = pools.get(e.bucket)
                if rows is None:
                    rows = pools[e.bucket] = ProgramRowSet(e.bucket,
                                                           prog.width)
                rows.assign(rows.free_slots()[0], e)
        self._live_rows = sum(len(g.live_slots()) for g in pools.values())

    def _step_program_rows(self, pools) -> None:
        """One batched compiled call per program bucket with live rows:
        expire stale rows, pad the rest to the program's smallest fitting
        width, execute, retire everything with its value — the one-shot
        analog of a decode step, interleaved with LM prefill chunks and
        decode steps in the same worker iteration."""
        for bucket, rows in list(pools.items()):
            if not isinstance(rows, ProgramRowSet):
                continue
            prog = self._programs[bucket[0]]
            now = self._clock()
            for i in rows.occupied_slots():
                dl = rows.entries[i].request.deadline
                if dl is not None and dl <= now:
                    self._retire_program_row(
                        rows, i, STATUS_EXPIRED, now,
                        reason=f"deadline {dl} passed before the program "
                               f"step (now {now})")
            live = rows.occupied_slots()
            if not live:
                continue
            entries = [rows.entries[i] for i in live]
            try:
                faults.fire("serve.program_step",
                            path=f"{bucket[0]}-{len(entries)}")
                t0 = time.perf_counter()
                values = prog.step(bucket[1:],
                                   [e.request for e in entries])
            except Exception as exc:
                self._fail_program_rows(rows, exc)
                continue
            wall = time.perf_counter() - t0
            self.metrics.record_step(
                bucket, len(live), rows.width, wall, label=bucket[0])
            self.flight.record(
                "step", bucket=list(bucket), rows=len(live), seconds=wall,
                queue_depth=self._queue.count, compiles=_compile_count())
            now = self._clock()
            for i, val in zip(live, values):
                self._retire_program_row(rows, i, STATUS_OK, now, value=val)
        self._live_rows = sum(len(g.live_slots()) for g in pools.values())

    def _retire_program_row(self, rows, slot: int, status: str, now: float,
                            value=None, reason: str = "") -> None:
        """Retire one program row and free its slot — the only path a
        program row leaves its rowset by (the exactly-once release runs in
        :meth:`_retire` as for every other row). A one-shot answer IS the
        first output, so ``ttft_s`` equals ``total_s``."""
        e = rows.entries[slot]
        metrics = {"bucket": rows.bucket, "slot": slot, "queue_s": e.queue_s,
                   "ttft_s": now - e.enq_t, "total_s": now - e.enq_t}
        if status == STATUS_OK:
            result = Result(e.request.rid, STATUS_OK, value=value,
                            metrics=metrics)
        else:
            result = Result(e.request.rid, status, reason=reason,
                            metrics=metrics)
        rows.release(slot)
        self._retire(e, result)

    def _fail_program_rows(self, rows, exc: Exception) -> None:
        """A program step died: rows with attempt budget left requeue for
        a transparent retry; the rest fail with error Results — only this
        bucket's rows are touched (a program step holds no donated slab,
        so there is nothing to escalate)."""
        reason = f"program step failed: {type(exc).__name__}: {exc}"
        self.flight.record("program_fault", bucket=list(rows.bucket),
                           rows=len(rows.occupied_slots()), error=reason,
                           queue_depth=self._queue.count,
                           compiles=_compile_count())
        now = self._clock()
        for i in rows.occupied_slots():
            e = rows.entries[i]
            if e.attempts_left():
                rows.release(i)
                self._requeue(e, reason)
            else:
                self._retire_program_row(rows, i, STATUS_ERROR, now,
                                         reason=reason)
        self._flight_dump("program-step-failed")

    def _requeue(self, entry: _Entry, reason: str) -> None:
        """Park a failed attempt back in the former for its next attempt
        (the caller checked ``attempts_left``). The admission reservation
        is CARRIED — never released, never re-charged — so a parked retry
        holds exactly its one slot of the queue depth and KV HBM budget.
        On a shutting-down engine the retry would never be claimed, so it
        retires with the failure instead of stranding."""
        twin = entry.retry()
        with self._cond:
            requeued = self._state in ("running", "draining")
            if requeued:
                self._former.add(twin)
                self._cond.notify_all()
        if not requeued:
            self._retire(twin, Result(
                twin.request.rid, STATUS_ERROR,
                reason=f"{reason} (engine shutting down before retry)"))
            return
        with obs_trace.use(entry.trace):
            self.metrics.record_retry(entry.request.rid, twin.attempt,
                                      entry.request.max_attempts, reason)

    # ------------------------------------------------- supervised recovery

    def attach_supervisor(self, on_crash) -> None:
        """Install the supervisor's crash kick: while set, a dying worker
        stashes its undone entries for :meth:`_recover` instead of failing
        them, and calls ``on_crash()`` so recovery starts promptly."""
        self._on_crash = on_crash

    def detach_supervisor(self) -> None:
        self._on_crash = None

    def _recover(self, reason: str, respawn: bool = True) -> dict:
        """Recover from a dead or stuck worker generation: supersede it
        (``_gen`` bump — a stale thread exits at its next check and can
        never retire a superseded entry), requeue every undone in-flight
        entry within its attempt budget (the rest fail with ``error``),
        drop the row groups and the page pool — their state died with the
        worker; both rebuild zeroed on the next admission — and spawn a
        fresh worker thread.
        Queued (former) entries are untouched: they were never in flight.
        ``respawn=False`` is the breaker's terminal path: supersede and
        fail everything held, mark the old thread abandoned (it may be
        wedged in a device call forever — shutdown must not join it), and
        spawn nothing. Returns counts for the supervisor's EventLog
        record."""
        failed, twins = [], []
        with self._cond:
            self._gen += 1
            gen = self._gen
            alive = respawn and self._state in ("running", "draining")
            if self._crash is not None:
                stash = list(self._crash[1])
                self._crash = None
            else:
                # stuck path: steal what the stale (still-alive) worker
                # holds — its pools/claimed mirrors. The straggler
                # mutates pool.entries WITHOUT this lock, so snapshot each
                # list and skip holes rather than indexing live_slots()
                # (an entry it retires concurrently shows up handle-done
                # below and is skipped; one it frees mid-scan must not
                # crash the recovery)
                stash = [e for p in self._pools.values()
                         for e in list(p.entries) if e is not None]
                stash += list(self._claimed)
                if self._pipe is not None:
                    # rows that left their slots with their last call in
                    # flight are in no group
                    stash += self._pipe.leaving()
            self._pools = {}
            self._pipe = None
            self._claimed = []
            # the paged pool's slab/block-table/prefix-cache state died
            # with the worker: drop it wholesale; it rebuilds zeroed on
            # the fresh generation's first admission (page-unit admission
            # reservations ride the requeued twins, never re-charged)
            if self._kvpool is not None:
                memledger.get_ledger().free(f"kvpool:{self._name}",
                                            strict=False)
            self._kvpool = None
            seen = set()
            for e in stash:
                if id(e) in seen or e.handle.done() or e.superseded:
                    continue
                seen.add(id(e))
                if alive and e.attempts_left():
                    twin = e.retry()
                    self._former.add(twin)
                    twins.append(twin)
                else:
                    failed.append(e)
            if alive:
                self._thread = self._make_thread(gen)
            elif not respawn:
                self._abandoned = self._thread
            started = self._started
            # grant the fresh generation a full watchdog window: without
            # this the stale generation's last stamp re-trips the watchdog
            # before the new worker's first iteration, and repeated
            # recoveries burn the attempt budget on a worker that never got
            # to run
            self._heartbeat = time.monotonic()
            self._cond.notify_all()
        for e in failed:
            self._retire(e, Result(
                e.request.rid, STATUS_ERROR,
                reason=f"worker lost and attempt budget exhausted: "
                       f"{reason}"))
        for t in twins:
            with obs_trace.use(t.trace):
                self.metrics.record_retry(t.request.rid, t.attempt,
                                          t.request.max_attempts, reason)
        # analyze: single-writer — a progress gauge for the watchdog, owned
        # by the live scheduler generation; _recover zeroes it only after
        # the generation it superseded stopped (int stores are atomic)
        self._live_rows = 0
        if alive and started:
            self._thread.start()
        return {"gen": gen, "requeued": len(twins), "failed": len(failed)}

    # ------------------------------------------------ cross-engine migration

    def freeze_rows(self) -> dict | None:
        """Pause this engine at a step boundary and take ownership of every
        resident row for migration: admission closes, the worker parks at
        its next iteration top (state ``freezing`` — pools left intact),
        and the caller thread exports each row's KV pages + cursors into a
        CRC-framed host blob (:meth:`PagedKVPool.export_rows`).

        Returns ``{"engine", "blob", "entries", "queued", "fallback"}``:
        ``entries`` maps rid → the live in-process :class:`_Entry` (handle
        + admission reservation — both travel with the row, the blob only
        carries device/cursor state); ``queued`` is the former backlog
        (never started — moved wholesale, no retry twin); ``fallback`` is
        rows that could not export (a ``serve.migrate`` export fault, or a
        worker crash mid-freeze — the pool is not trusted after one) and
        must ride the PR 7 retry path. Returns None when the engine cannot
        freeze (already terminal) — the caller falls back to a plain drain.
        Terminal either way once it returns a dict: the worker has exited
        and the router closes the engine next."""
        self._refuse_private("freeze_rows")
        self._queue.close("engine freezing for migration")
        with self._cond:
            if self._state not in ("running", "draining"):
                return None
            self._state = "freezing"
            self._cond.notify_all()
        self._join_worker()
        self._fail_mig_inbox("engine froze for migration")
        with self._cond:
            crash = self._crash
            self._crash = None
            pools = dict(self._pools)
            pool = self._kvpool
            queued = self._former.take_all()
        entries: dict = {}
        rows: list[dict] = []
        fallback: list = []
        seen: set[int] = set()

        def _viable(e) -> bool:
            if (e is None or id(e) in seen or e.superseded
                    or e.handle.done()):
                return False
            seen.add(id(e))
            return True

        if crash is not None:
            # the worker died mid-freeze: the pool is not trusted —
            # export nothing, every stashed row rides the retry fallback.
            # This is also how a dead generation's in-flight export is
            # invalidated: its rows become fresh-attempt twins, and the
            # stale export's entries (superseded by those twins) are
            # skipped at adopt time
            for e in crash[1]:
                if _viable(e):
                    fallback.append(e)
        else:
            for bucket, group in pools.items():
                if isinstance(group, ProgramRowSet):
                    # one-shot program rows have no KV state to export: the
                    # program's freeze hook may veto, otherwise they ride
                    # the fallback lane and re-execute on the target
                    # (exactly-once is the handle's, not the row's)
                    prog = self._programs.get(bucket[0])
                    for slot in group.occupied_slots():
                        e = group.entries[slot]
                        if not _viable(e):
                            continue
                        if prog is not None:
                            prog.freeze(e)
                        fallback.append(e)
                    continue
                for slot in group.occupied_slots():
                    e = group.entries[slot]
                    if not _viable(e):
                        continue
                    try:
                        faults.fire(
                            "serve.migrate",
                            path=f"export:{e.request.rid}@{self._name}")
                        rows.append(self._export_row(group, bucket, slot))
                        entries[e.request.rid] = e
                    except Exception:
                        fallback.append(e)
        blob = None
        if rows and pool is not None:
            try:
                blob = pool.export_rows(rows)
                self.metrics.record_migration("export", len(rows))
            except Exception:
                # the blob never materialized: every exported row falls
                # back to the retry path (its source pages die with this
                # engine — nothing leaks into the blob's absence)
                fallback.extend(entries.values())
                entries = {}
        token = None
        if blob is not None:
            # the frozen blob is migration bytes in flight: credit it to
            # this engine until the adopt side consumes it (adopt_rows
            # transfers ownership to the target, then debits exactly once;
            # a never-adopted blob is swept by _finalize_obs's free_owner)
            token = f"migration:{self._name}:{next(_mig_tokens)}"
            memledger.get_ledger().register(token, len(blob), "migration",
                                            owner=self._name)
        with self._cond:
            self._state = "frozen"
        self._flight_dump("freeze")
        return {"engine": self, "blob": blob, "entries": entries,
                "queued": list(queued), "fallback": fallback,
                "ledger_token": token}

    def _export_row(self, group, bucket, slot: int) -> dict:
        """One row's migration manifest: block table (position order),
        cursors, host token stream, and sampling state — everything
        :meth:`PagedGroup.restore` needs for a bit-identical resume."""
        e = group.entries[slot]
        return {
            "rid": e.request.rid,
            "bucket": [int(b) for b in bucket],
            "prompt": np.asarray(e.request.prompt, np.int32).tolist(),
            "pages": [int(p) for p in (group.row_pages[slot] or [])],
            "length": int(group.lengths[slot]),
            "position": int(group.positions[slot]),
            "steps_done": int(group.steps_done[slot]),
            "cur_tok": int(group.cur_tok[slot]),
            "pf_next": int(group.pf_next[slot]),
            "n_shared": int(group.shared_pages[slot]),
            "emitted": [int(t) for t in (group.emitted[slot] or [])],
            "seed": int(e.request.seed),
            "temperature": float(group.temperature[slot]),
            "top_p": float(group.top_p[slot]),
            "top_k": int(group.top_k[slot]),
            "ttft_s": group.ttft_s[slot],
            # the request's span context rides the manifest so an adopting
            # engine — even in another process, where no live _Entry span
            # exists — continues the SAME trace instead of orphaning it
            "trace": (None if e.trace is None else {
                "trace_id": e.trace.trace_id, "span_id": e.trace.span_id,
                "parent_id": e.trace.parent_id, "name": e.trace.name}),
        }

    def adopt_rows(self, frozen: dict, timeout: float | None = None) -> dict:
        """Adopt a peer's frozen row set: import the blob's KV pages into
        this engine's pool (re-deduplicating through the prefix cache) and
        resume each row mid-stream. Runs on THIS engine's worker thread via
        the migration mailbox — the pool stays single-threaded. Each row
        binds under the engine lock: its admission reservation is adopted
        (:meth:`AdmissionQueue.adopt`) at bind time and released by the
        normal retirement path, so the reservation is carried exactly once
        end to end (the caller releases the source's charge for adopted
        rids). Rows whose entry was superseded or resolved while frozen
        (a source recovery invalidated the export) are dropped with their
        pages released. Returns ``{"adopted": [rids], "fallback":
        [entries]}``; on a worker timeout the rows bound so far count as
        adopted and the rest fall back — never both."""
        entries = dict(frozen["entries"])
        blob = frozen.get("blob")
        if blob is None or not entries:
            return {"adopted": [], "fallback": list(entries.values())}
        self._refuse_private("adopt_rows")
        if timeout is None:
            timeout = get_config().serve_migrate_timeout_s
        box: dict = {"bound": [], "cancelled": False}
        ev = threading.Event()
        with self._cond:
            if self._state != "running" or not self._started:
                raise MigrationError(
                    f"adopt target {self._name} not accepting "
                    f"({self._state})")
            self._mig_inbox.append(
                ("adopt", {"blob": blob, "entries": entries}, ev, box))
            if self._idle:
                self._heartbeat = time.monotonic()
            self._cond.notify_all()
        # the handoff is committed: the blob's ledger entry moves to this
        # engine (source debited, target credited — one transfer, the
        # process total never moves) and is debited exactly once below,
        # whichever way the adopt resolves (bound, timeout, or error —
        # after the post the blob is consumed or dead either way). The
        # not-accepting raise above leaves the entry with the source, so
        # a retry against another replica still finds it.
        token = frozen.get("ledger_token")
        led = memledger.get_ledger()
        if token:
            led.transfer(token, owner=self._name)
        try:
            if not ev.wait(timeout):
                # cancel under the lock: rows not yet bound will be released
                # by the worker when it gets there; rows already bound are
                # this engine's responsibility now — report them adopted so
                # the caller neither twins nor re-places them
                with self._cond:
                    box["cancelled"] = True
                    bound = set(box["bound"])
                return {"adopted": sorted(bound),
                        "fallback": [e for rid, e in entries.items()
                                     if rid not in bound]}
            err = box.get("error")
            if err is not None:
                if isinstance(err, MigrationError):
                    raise err
                raise MigrationError(
                    f"adopt failed on {self._name}: {type(err).__name__}: "
                    f"{err}") from err
            return box["result"]
        finally:
            if token:
                led.free(token, strict=False)

    def adopt_entries(self, entries) -> bool:
        """Queue-only handoff for migrated work WITHOUT device state — the
        frozen backlog and retry-fallback twins. Each entry's reservation
        is force-admitted (the fleet already admitted this work; the gate
        bounds new admissions only) and the entry queues normally. Returns
        False when this engine is not accepting — the caller tries the
        next replica."""
        entries = list(entries)
        if not entries:
            return True
        with self._cond:
            if self._state != "running":
                return False
            for e in entries:
                self._queue.adopt(e.cost)
                self._former.add(e)
            if self._idle:
                self._heartbeat = time.monotonic()
            self._cond.notify_all()
        self.metrics.record_queue(self._queue.count,
                                  self._queue.bytes_in_flight)
        return True

    def export_prefixes(self, n: int,
                        timeout: float | None = None) -> bytes | None:
        """The pool's N hottest prefix-cache chains as a migration blob
        (worker-mediated; best-effort — returns None instead of raising:
        cache warming must never fail a restart)."""
        if n <= 0:
            return None
        self._refuse_private("export_prefixes")
        if timeout is None:
            timeout = get_config().serve_migrate_timeout_s
        try:
            return self._mig_post("export_prefixes", int(n), timeout)
        except MigrationError:
            return None

    def import_prefixes(self, blob: bytes | None,
                        timeout: float | None = None) -> int:
        """Warm this pool's prefix cache from a peer's exported chains
        (worker-mediated; best-effort). Returns entries inserted."""
        if not blob:
            return 0
        self._refuse_private("import_prefixes")
        if timeout is None:
            timeout = get_config().serve_migrate_timeout_s
        try:
            return int(self._mig_post("import_prefixes", blob, timeout) or 0)
        except MigrationError:
            return 0

    def _refuse_private(self, what: str) -> None:
        """Migration moves a row's pages (and, for prefixes, the cache's); a
        window layer's ring is neither shared nor serialized yet, and a
        row's pages are no use without its recurrent state, which is shared
        by snapshot inside ONE pool (``prefix_cache``) but is not
        serialized, nor is a snapshot: for such a model these entry points
        raise rather than move half a row."""
        if self._ring:
            raise MigrationError(
                f"{what}: {self._name} serves a model with sliding-window "
                f"layers; migrating or sharing a window layer's pages is "
                f"not built")
        if self._state_slots:
            raise MigrationError(
                f"{what}: {self._name} serves a model with recurrent "
                f"mixers; a row's pages are no use without the recurrent "
                f"state at their end, and serializing a state slot or a "
                f"snapshot is not built")

    def _mig_post(self, kind: str, payload, timeout: float):
        """Post one op to the worker's migration mailbox and wait."""
        box: dict = {"bound": [], "cancelled": False}
        ev = threading.Event()
        with self._cond:
            if self._state != "running" or not self._started:
                raise MigrationError(
                    f"{self._name} not accepting ({self._state})")
            self._mig_inbox.append((kind, payload, ev, box))
            if self._idle:
                self._heartbeat = time.monotonic()
            self._cond.notify_all()
        if not ev.wait(timeout):
            with self._cond:
                box["cancelled"] = True
            raise MigrationError(
                f"{kind} timed out after {timeout}s on {self._name}")
        err = box.get("error")
        if err is not None:
            raise MigrationError(
                f"{kind} failed on {self._name}: {type(err).__name__}: "
                f"{err}") from err
        return box.get("result")

    def _service_migrations(self, pool, pools, pf_queue) -> None:
        """Drain the migration mailbox on the worker thread (called once
        per iteration). Any failure lands in the requester's box — the
        worker survives every migration fault; mid-migration failure must
        degrade to the retry path, never kill the adoptive engine."""
        while True:
            with self._cond:
                if not self._mig_inbox:
                    return
                kind, payload, ev, box = self._mig_inbox.popleft()
                if box.get("cancelled"):
                    box["error"] = MigrationError("cancelled by requester")
                    ev.set()
                    continue
            try:
                if kind == "adopt":
                    box["result"] = self._mig_adopt(pool, pools, pf_queue,
                                                    payload, box)
                elif kind == "export_prefixes":
                    box["result"] = pool.export_prefixes(payload)
                elif kind == "import_prefixes":
                    faults.fire("serve.migrate", path=f"warm@{self._name}")
                    n = pool.import_prefixes(payload)
                    self._record_pages(pool)
                    box["result"] = n
                else:
                    box["error"] = MigrationError(
                        f"unknown migration op {kind!r}")
            except BaseException as exc:
                box["error"] = exc
            ev.set()

    def _mig_adopt(self, pool, pools, pf_queue, payload, box) -> dict:
        """Worker-side adopt: import the blob, then bind each row under
        the engine lock (atomic against the requester's timeout-cancel —
        a row is either bound here exactly once or reported back for the
        fallback path, never both)."""
        faults.fire("serve.migrate", path=f"import@{self._name}")
        entries = payload["entries"]
        rows = pool.import_rows(payload["blob"])
        adopted: list = []
        fallback: list = []
        for row in rows:
            rid = row["rid"]
            e = entries.get(rid)
            pages = row["pages"]
            bucket = tuple(row["bucket"])
            group = pools.get(bucket)
            if group is None and bucket in self.buckets:
                group = pools[bucket] = self._new_group(bucket)
            bound = False
            try:
                faults.fire("serve.migrate",
                            path=f"adopt:{rid}@{self._name}")
                with self._cond:
                    viable = (e is not None and not e.superseded
                              and not e.handle.done()
                              and not box.get("cancelled")
                              and self._state == "running")
                    free = group.free_slots() if group is not None else []
                    if viable and free:
                        slot = free[0]
                        self._queue.adopt(e.cost)
                        group.restore(slot, e, row, pages)
                        if int(row["pf_next"]) >= 0:
                            pf_queue.append((bucket, slot, rid))
                        box["bound"].append(rid)
                        bound = True
            except Exception:
                bound = False
            if bound:
                adopted.append(rid)
                # re-activate the request's trace across the hop: a cross-
                # process adopt has no live entry span, so rebuild it from
                # the manifest; either way the migration itself becomes a
                # child span, so freeze -> adopt -> result joins into one
                # trace_id in the JSONL (tests/test_migration.py asserts)
                base = e.trace
                t = row.get("trace")
                if base is None and t:
                    base = obs_trace.SpanContext(
                        t.get("trace_id"), t.get("span_id"),
                        t.get("parent_id"),
                        t.get("name") or f"serve.request.{rid}")
                if base is not None:
                    e.trace = base.child(f"serve.migrate.{rid}")
                with obs_trace.use(e.trace):
                    self.metrics.record_page_event(
                        "adopt", rid=rid, pages=len(pages),
                        shared=int(row["n_shared"]),
                        used=pool.used_count(), total=pool.capacity)
            else:
                pool.release(pages)
                if (e is not None and not e.superseded
                        and not e.handle.done()):
                    fallback.append(e)
        if adopted:
            self.metrics.record_migration("adopt", len(adopted))
        self._record_pages(pool)
        self._live_rows = sum(len(g.live_slots())
                              for g in pools.values())
        return {"adopted": adopted, "fallback": fallback}

    def _fail_mig_inbox(self, reason: str) -> None:
        """Resolve every pending mailbox op with an error (the worker is
        gone — a requester blocked on its event must not wait out the
        full timeout)."""
        while True:
            with self._cond:
                if not self._mig_inbox:
                    return
                kind, payload, ev, box = self._mig_inbox.popleft()
            box["error"] = MigrationError(reason)
            ev.set()

    def kvpool_audit(self) -> dict:
        """The pool invariant report (:meth:`PagedKVPool.audit`) over this
        engine's live groups — exact on a quiesced engine (closed, drained,
        frozen); advisory under a running worker (the probe snapshot races
        row transitions). Never raises — rides ``GET /debug/kvpool``."""
        with self._cond:
            pool = self._kvpool
            groups = [g for g in self._pools.values()
                      if not isinstance(g, ProgramRowSet)]
        if pool is None:
            return {"ok": True, "errors": [], "note": "no pool built"}
        try:
            return pool.audit(groups)
        except Exception as exc:  # racing a live worker's row transition
            return {"ok": False,
                    "errors": [f"audit crashed: {type(exc).__name__}: "
                               f"{exc}"]}

    # --------------------------------------------------- paged scheduler

    def _run_paged(self, gen: int) -> None:
        """The paged slot-step loop, a pipeline ONE call deep: the worker
        lands a result only after it has dispatched the work that follows
        it. Each iteration refills freed rows from the queue (page
        allocation + prefix match — host-side), dispatches prefill chunks
        up to the ``serve_prefill_chunk`` TOKEN budget (oldest rows first;
        no chunk is waited for here), dispatches one decode step over the
        live rows of every bucket, packed into one call
        (:meth:`_step_paged`), and only then lands the PREVIOUS decode call
        (its tokens announced, its rows retired) and the first tokens of
        this iteration's final chunks — while the chip runs what was just
        dispatched. The tokens of a call in flight stay on the device: the
        next call is fed from them (:class:`_Pipeline`), and the host's
        cursors (``positions``, ``steps_done``) advance at dispatch; a row
        whose step budget ends with the call in flight has left its slot for
        the next claim to refill (:meth:`_leave_paged`).
        Chunked prefill interleaves with decode, so a long prompt never
        monopolizes an iteration. ``pools`` maps bucket -> PagedGroup
        over the engine's
        one shared :class:`PagedKVPool`; ``pf_queue`` is the FIFO of rows
        mid-prefill ((bucket, slot, rid) — rid guards against a retired
        slot's re-occupant inheriting a stale cursor); ``pipe`` is what is
        in flight. Every way out lands it first (:meth:`_drain_paged`), but
        a superseded generation's, whose rows the recovery already requeued.
        ``self._pools``/``self._claimed``/``self._pipe`` mirror the worker's
        hands so a supervisor recovering a STUCK generation (watchdog timeout — the
        thread is alive but unreachable) can still find every in-flight
        entry to requeue."""
        pools: dict[tuple, PagedGroup] = {}
        pipe = _Pipeline(self.max_batch)
        with self._cond:
            if self._gen != gen:
                return  # superseded before the first iteration
            self._pools = pools
            self._pipe = pipe
            # the GENERATION-LOCAL pool binding: every helper below takes
            # this pool, never self._kvpool — a stuck-but-alive superseded
            # worker resuming mid-iteration must mutate only its own dead
            # pool, not the replacement generation's (page ids are
            # meaningless across pools; a cross-generation release would
            # silently double-book pages under live rows). Bound UNDER the
            # lock with the generation re-checked, so a racing recovery
            # can never hand two generations one pool.
            pool = self._ensure_kvpool()
        pf_queue: collections.deque = collections.deque()
        claimed: list[_Entry] = []
        try:
            while True:
                if self._gen == gen:  # a superseded straggler must never
                    # analyze: single-writer — generation-guarded monotonic
                    # stamp; floats assign atomically under the GIL and the
                    # watchdog tolerates any interleaving
                    self._heartbeat = time.monotonic()  # fake a live pulse
                if self._slo is not None:
                    # rate-limited internally (serve_slo_eval_interval_s):
                    # per-iteration cost is one float compare
                    self._slo.tick(self._clock())
                faults.fire("serve.worker_crash",
                            path=threading.current_thread().name)
                claimed = []
                freezing = False
                with self._cond:
                    while True:
                        with annotate("serve.claim") as span:
                            if self._gen != gen:
                                return  # superseded by a recovery
                            if self._state == "freezing":
                                # migration pause: park WITHOUT touching
                                # the pools — freeze_rows() joins this
                                # thread and takes over every resident row
                                # (what is in flight lands first, below,
                                # outside the lock)
                                freezing = True
                                break
                            # a call in flight still has to land, though
                            # no row of it may hold a slot any more (they
                            # left with their last step out, or were
                            # retired under it)
                            busy = pipe.busy() or any(
                                p.occupied_slots() for p in pools.values())
                            if self._mig_inbox:
                                break  # service migration ops unlocked
                            if self._state == "closing":
                                # resident rows (live AND mid-prefill) are
                                # the work in flight: finish them (close()
                                # already emptied the former)
                                if not busy:
                                    return
                                break
                            draining = self._state == "draining"
                            claimed = self._claim(pools)
                            span.set_metadata(claimed=len(claimed))
                            if claimed or busy:
                                break
                            if draining:
                                return  # nothing queued, nothing resident
                        self._idle = True
                        with annotate("serve.wait"):
                            self._cond.wait(None if self._real_clock
                                            else _POLL_CAP_S)
                        self._idle = False
                        if self._gen == gen:
                            self._heartbeat = time.monotonic()
                    self._claimed = claimed
                if freezing:
                    # freeze_rows exports positions / cur_tok / emitted:
                    # they must be the landed ones
                    self._drain_paged(pool, pools, pipe)
                    return
                # the iteration that does the work: opened once the claim
                # (and any wait) has returned, so its counts are this
                # iteration's and an idle engine holds no open span. The
                # counts cost a pass over the resident rows: taken only
                # while a trace is
                with annotate("serve.iter") as span:
                    if span.is_enabled():
                        span.set_metadata(**self._paged_counts(pool, pools))
                    with self._cond:
                        if self._gen == gen and pool is not self._kvpool:
                            # this generation dropped its pool (slab
                            # consumed by a failed donated call): rebind to
                            # the rebuilt one — the old object's arrays are
                            # deleted. Under the lock + gen check: a stale
                            # generation must never build (or adopt) the
                            # live generation's pool
                            pool = self._ensure_kvpool()
                            pipe.reset_feed()
                    self._service_migrations(pool, pools, pf_queue)
                    prog_claimed = [e for e in claimed
                                    if self._is_program_bucket(e.bucket)]
                    lm_claimed = [e for e in claimed
                                  if not self._is_program_bucket(e.bucket)]
                    self._admit_paged(pool, pools, lm_claimed, pf_queue)
                    self._admit_program_rows(pools, prog_claimed)
                    claimed = []
                    with self._cond:
                        if self._gen == gen:  # never clobber a successor's
                            self._claimed = []  # claimed mirror
                    self._prefill_paged_chunk(pool, pools, pf_queue, pipe)
                    self._step_paged(pool, pools, pipe)
                    self._step_program_rows(pools)
        except BaseException as exc:  # worker death: recover or fail held
            # (what was in flight is dropped with the generation: its rows
            # are still in their slots, or in the flight's ``leaving``, so
            # the handler finds every one)
            held = [p.entries[i] for p in pools.values()
                    for i in p.occupied_slots()]
            if self._crash_handler(exc, claimed + held + pipe.leaving(),
                                   gen):
                return
            raise

    def _paged_counts(self, pool, pools) -> dict:
        """The ``serve.iter`` span's fields: the queue and the KV pool as
        an iteration begins, its claim made (``queue_depth`` as the step
        records have it: requests pending or in flight). ``row_pages`` are
        the pages resident LM rows hold (reserved up front for prompt +
        steps), ``kv_tokens`` the positions of them that are written (a row
        mid-prefill has its prefilled prompt), ``pages_used`` adds what the
        prefix cache keeps, ``shared_pages`` are the pages with more than one
        referent (rows and the cache) and ``cached_pages`` the cache's
        entries; ``snapshot_slots`` the state snapshots the pool has room
        for and ``snapshots_held`` those the cache's entries own."""
        resident = live = row_pages = window_pages = kv_tokens = 0
        for g in pools.values():
            if isinstance(g, ProgramRowSet):
                continue
            for i in g.occupied_slots():
                resident += 1
                row_pages += len(g.row_pages[i] or ())
                window_pages += len(g.window_row_pages[i] or ())
                if g.pf_next[i] < 0:
                    live += 1
                    # analyze: ignore[host-sync] — host numpy bookkeeping
                    kv_tokens += int(g.positions[i])
                else:
                    # analyze: ignore[host-sync] — host numpy bookkeeping
                    kv_tokens += int(min(g.pf_next[i], g.lengths[i]))
        out = {"queue_depth": self._queue.count, "resident_rows": resident,
               "live_rows": live, "row_pages": row_pages,
               "pages_used": pool.used_count(),
               "pages_total": pool.capacity, "kv_tokens": kv_tokens,
               "shared_pages": pool.shared_count(),
               "cached_pages": pool.cached_count()}
        if self._spec is not None:
            # both classes beside their sum: a row's global table covers
            # every position, its window ring a bounded few
            out.update(row_pages=row_pages + window_pages,
                       global_pages=row_pages, window_pages=window_pages)
        if self._state_slots:
            # every resident row holds one slot, prefilling or live
            out.update(state_slots=self._state_slots - 1,
                       state_rows=resident,
                       state_bytes=resident * self._state_slot_bytes)
        if self._snapshot_slots:
            out.update(snapshot_slots=self._snapshot_slots,
                       snapshots_held=pool.snapshots_held())
        return out

    def _admit_paged(self, pool, pools, claimed, pf_queue) -> None:
        """Bind each claimed entry to a free row of its bucket's group:
        prefix-cache match, page allocation (the admission charge was
        taken in page units at submit, so the alloc cannot fail under
        engine traffic — still guarded), block table build. Host-side
        only; the device work happens chunk by chunk in
        :meth:`_prefill_paged_chunk`."""
        if not claimed:
            return
        from ..models.planner import request_pages

        # dispatch order ACROSS buckets: _claim walks an unordered
        # bucket set, but the prefill queue is the TTFT ledger — higher
        # priority first, then arrival (rid is monotonic per process), so a
        # short early request never waits out a later long prompt's chunks
        claimed = sorted(claimed,
                         key=lambda e: (-e.request.priority, e.request.rid))
        for e in claimed:
            with obs_trace.use(e.trace), annotate(
                    "serve.admit", rid=e.request.rid,
                    bucket=_bucket_tag(e.bucket)) as span:
                now = self._clock()
                r = e.request
                if r.deadline is not None and r.deadline <= now:
                    self._retire(e, Result(
                        r.rid, STATUS_EXPIRED,
                        reason=f"deadline {r.deadline} passed before "
                               f"dispatch (dispatched at {now})",
                        metrics={"bucket": e.bucket,
                                 "queue_s": now - e.enq_t,
                                 "total_s": now - e.enq_t}))
                    continue
                e.queue_s = now - e.enq_t
                group = pools.get(e.bucket)
                if group is None:
                    group = pools[e.bucket] = self._new_group(e.bucket)
                slot = group.free_slots()[0]
                n = r.prompt.shape[0]
                shared_len, spages, snap, seen_len = \
                    pool.match_prefix_state(r.prompt)
                need = request_pages(n, r.steps, self._page_len)
                wpages, state_id = [], 0
                try:
                    if self._ring:
                        wpages = pool.alloc_window(request_pages(
                            n, r.steps, self._page_len, ring=self._ring))
                    state_id = pool.alloc_state()
                    owned = pool.alloc(need - len(spages))
                except PagePoolExhausted as exc:
                    pool.release(spages)  # drop the refs the match took
                    pool.release_window(wpages)
                    pool.release_state(state_id)
                    # the OOM post-mortem lands BEFORE the retry path runs
                    # (the retry rebuilds state and destroys the evidence)
                    memledger.dump_oom_forensics(
                        f"page allocation failed for rid {r.rid}: {exc}")
                    reason = f"page allocation failed: {exc}"
                    if e.attempts_left():
                        self._requeue(e, reason)
                    else:
                        self._retire(e, Result(
                            r.rid, STATUS_ERROR, reason=reason,
                            metrics={"bucket": e.bucket,
                                     "queue_s": e.queue_s,
                                     "total_s": now - e.enq_t}))
                    continue
                group.assign(slot, e, spages + owned, shared_len,
                             len(spages), wpages, state_id)
                group.seen_len[slot] = seen_len if seen_len > shared_len else 0
                if snap:
                    # the state after the shared prefix, into the row's
                    # slot: in the stream ahead of the row's first chunk,
                    # which starts at shared_len and so enters from it
                    pool.copy_state(snap, state_id)
                pf_queue.append((e.bucket, slot, r.rid))
                self.metrics.record_prefix(hit=bool(spages))
                self.metrics.record_page_event(
                    "alloc", rid=r.rid, pages=len(spages) + len(owned),
                    shared=len(spages), used=pool.used_count(),
                    total=pool.capacity)
                span.set_metadata(queue_wait_ms=1e3 * e.queue_s,
                                  pages=len(spages) + len(owned),
                                  shared_pages=len(spages),
                                  prompt_tokens=n, shared_tokens=shared_len)
                if self._snapshot_slots:
                    # the boundary the row's state was copied from
                    span.set_metadata(snapshot_tokens=shared_len)
        self._record_pages(pool)

    def _prefill_paged_chunk(self, pool, pools, pf_queue, pipe) -> None:
        """Dispatch bounded prefill for this iteration — the chunked-prefill
        scheduling contract: at most ``serve_prefill_chunk`` prompt TOKENS
        of prefill per worker iteration (several short prompts may share
        the budget; one long prompt consumes it in a single chunk and
        resumes next iteration), decode steps interleaving in between so a
        long prompt never monopolizes the worker. Rows prefill oldest
        first — FIFO TTFT fairness. No chunk is waited for here: a chunk's
        pages reach the next program by data dependence, and a row's final
        chunk (the one containing the prompt's last token) flips the row
        decode-ready with its first token still on the device —
        :meth:`_land_first` brings it, after this iteration's decode
        dispatch."""
        budget = self._prefill_chunk
        chunks = 0
        with annotate("serve.prefill") as span:
            while budget > 0 and pf_queue:
                used = self._prefill_one_chunk(pool, pools, pf_queue, pipe)
                budget -= used
                chunks += used > 0
            span.set_metadata(chunks=chunks,
                              tokens=self._prefill_chunk - budget)
        self._live_rows = sum(len(g.live_slots()) for g in pools.values())

    def _prefill_one_chunk(self, pool, pools, pf_queue, pipe) -> int:
        """Dispatch one chunk for the head of the prefill queue; returns the
        real prompt tokens it consumed (0 ends the caller's budget loop —
        nothing left to prefill, or the head row just failed). A chunk that
        is not final is never synced on (an error in it surfaces at the
        next landing, which finds the slab unusable and requeues every
        resident row); a final one joins ``pipe.firsts`` and its token the
        device feed, so the row rides this iteration's decode call."""
        while pf_queue:
            bucket, slot, rid = pf_queue[0]
            group = pools.get(bucket)
            e = group.entries[slot] if group is not None else None
            if (e is None or e.request.rid != rid
                    or group.pf_next[slot] < 0):
                pf_queue.popleft()  # stale: retired/expired/re-occupied
                continue
            break
        else:
            return 0
        with obs_trace.use(e.trace):
            r = e.request
            p, s = bucket
            # analyze: ignore[host-sync] — host numpy bookkeeping arrays
            cs = int(group.pf_next[slot])
            # analyze: ignore[host-sync] — host numpy bookkeeping arrays
            n = int(group.lengths[slot])
            C = group.chunk
            tokens = min(C, n - cs)
            final = cs + C >= n
            try:
                with annotate("serve.prefill.dispatch", rid=r.rid,
                              bucket=_bucket_tag(bucket), start=cs,
                              tokens=tokens, width=C,
                              final=int(final)) as dispatch:
                    if self._state_slots:
                        # the valid tokens the mixers' scan advances over
                        dispatch.set_metadata(**{self._mixer_tokens: tokens})
                        if self._spec.kda is not None:
                            # the blocks of the scan a layer runs for it
                            dispatch.set_metadata(
                                kda_blocks=self._spec.kda.scan_blocks(
                                    tokens, C))
                    chunk = group.prompts[slot][cs:cs + C]
                    if chunk.shape[0] < C:
                        # a prefix hit whose shared_len is page- but not
                        # CHUNK-aligned leaves a short tail slice; pad it
                        # back to the compiled width — a narrower array
                        # would compile a fresh program per width and
                        # break the <=3-per-bucket bound
                        chunk = np.concatenate(
                            [chunk, np.zeros(C - chunk.shape[0], np.int32)])
                    # copy-on-write gate on every page the chunk will
                    # scatter into (a no-op in steady state: writes target
                    # owned pages by construction —
                    # kvpool.PagedKVPool.ensure_writable)
                    for j in range(cs // self._page_len,
                                   min((cs + C) // self._page_len,
                                       group.pages_per_row)):
                        self._cow(pool, group, slot, j, rid=r.rid)
                    from ..models.transformer import lm_prefill_paged

                    faults.fire("serve.prefill", path=f"bucket-{p}x{s}")
                    t0 = time.perf_counter()
                    out = lm_prefill_paged(
                        self.params, pool.pages, group.prefill_tables(slot),
                        chunk, cs, n, heads=self.heads,
                        page_len=self._page_len,
                        seed=r.seed, temperature=r.temperature,
                        top_p=r.top_p, top_k=r.top_k,
                        compute_dtype=self.compute_dtype, moe=self.moe)
                    pages, first, counts = _program_result(out)
                    # the program's number, on the span that launched it (a
                    # span's fields cannot be set once it has closed)
                    seq = pipe.dispatched()
                    dispatch.set_metadata(seq=seq)
                    # analyze: ignore[host-sync] — host numpy bookkeeping
                    seen = int(group.seen_len[slot])
                    if pool.snapshot_due(cs + C, C, n, seen):
                        pool.pages = pages  # (donated to the copy below)
                        taken = self._take_snapshot(pool, group, slot,
                                                    cs + C)
                        pages = pool.pages
                        dispatch.set_metadata(snapshots=taken)
            except Exception as exc:
                pf_queue.popleft()
                self._paged_prefill_failure(pool, pools, bucket, slot, exc)
                return 0  # end this iteration's budget loop
            pool.pages = pages
            group.pf_next[slot] = cs + C
            self.flight.record(
                "prefill", bucket=[p, s], slot=slot, rid=r.rid,
                seconds=time.perf_counter() - t0, chunk=[cs, tokens],
                queue_depth=self._queue.count, compiles=_compile_count(),
                pages_used=pool.used_count())
            if not final:
                # nothing waits for this chunk: it has no interval of its
                # own (its device time is inside the next landing's)
                self.metrics.record_prefill(
                    e.bucket, 0.0, rid=r.rid, chunk=[cs, tokens],
                    final=False)
                return tokens
            pf_queue.popleft()
            group.begin_decode(slot)
            pipe.firsts.append(_First(group, slot, e, first, counts, t0,
                                      [cs, tokens], seq))
            if r.steps > 1:
                self._feed_first(pool, pools, pipe, group, slot, first)
        return tokens

    @staticmethod
    def _take_snapshot(pool, group, slot: int, position: int) -> int:
        """Copy the row's state, as the chunk just dispatched leaves it
        after ``position`` tokens, into a snapshot slot: in the stream
        behind that chunk and ahead of the row's next. The row owns the
        snapshot until its prefill lands (:meth:`_land_first` publishes it
        with the pages). Returns the snapshots taken: 0 where the pool has
        no slot to give, which costs the request nothing."""
        sid = pool.alloc_snapshot()
        if not sid:
            return 0
        group.snapshots[slot][position] = sid
        # analyze: ignore[host-sync] — host numpy bookkeeping arrays
        pool.copy_state(int(group.state_ids[slot]), sid)
        return 1

    def _feed_first(self, pool, pools, pipe, group, slot: int,
                    first) -> None:
        """Write a final chunk's token (a device scalar) into the feed, over
        an entry no continuing row reads, and point the row at it: the row
        rides the next decode call without the host having seen its token.
        Where every entry is taken (the call in flight is full of rows that
        go on) the pipeline drains instead and the token lands now, after
        the in-flight call's: a request's first-token record never precedes
        a step record its row was not in."""
        from ..models.transformer import feed_token

        if not pipe.free:
            self._drain_paged(pool, pools, pipe)
            return
        index = pipe.free.pop(0)
        pipe.feed = feed_token(pipe.feed, index, first)
        group.fed_serial[slot] = pipe.serial
        group.fed_index[slot] = index

    def _land_first(self, pool, pools, pipe, item: _First) -> None:
        """Bring a final chunk's first token to the host (the one place the
        worker waits for a prefill program): the ``prefill`` record with the
        token (``new_tokens == 1``, written now that it is here: TTFT is a
        real arrival time; its seconds are the interval since the landing
        before it, this chunk's alone only where its predecessor had landed:
        :meth:`_Pipeline.landed`), the
        prompt's pages published for prefix sharing, and the row retired
        where that token ends it. A row retired with its chunk in flight
        (deadline, a failed neighbour) is matched by entry and skipped."""
        group, slot, e = item.group, item.slot, item.entry
        r = e.request
        with obs_trace.use(e.trace):
            try:
                with annotate("serve.prefill.sync", rid=r.rid, final=1,
                              seq=item.seq) as sync:
                    first = int(item.first)  # device sync: the chunk landed
                    self._moe_counts(sync, item.counts)
            except Exception as exc:
                if group.entries[slot] is e:
                    self._paged_prefill_failure(pool, pools, group.bucket,
                                                slot, exc)
                return
            t = time.perf_counter()
            seconds, pipe.landed_t = t - max(item.t0, pipe.landed_t), t
            pipe.landed(item.seq)
            if group.entries[slot] is not e:
                return
            self.metrics.record_prefill(
                e.bucket, seconds, rid=r.rid, chunk=item.chunk, final=True)
            group.land_first(slot, first)
            group.ttft_s[slot] = self._clock() - e.enq_t
            # the prompt's full pages are final now — publish them for
            # copy-on-write reuse by later identical prefixes
            pool.insert_prefix(r.prompt, group.row_pages[slot],
                               group.snapshots[slot])
            self._record_pages(pool)
            if r.steps == 1 or (r.eos is not None and first == r.eos):
                self._retire_row_paged(pool, pools, group.bucket, slot,
                                       STATUS_OK, self._clock())

    def _drain_paged(self, pool, pools, pipe, then=None) -> None:
        """Land everything in flight, in the device's order: the decode
        call, then the first tokens of the chunks dispatched after it.
        ``then`` is the call just dispatched behind them, in flight from
        here on."""
        pipe.landing, pipe.call = pipe.call, then
        if pipe.landing is not None:
            self._land_paged(pool, pools, pipe, pipe.landing)
            pipe.landing = None
        firsts, pipe.firsts = pipe.firsts, []
        for item in firsts:
            self._land_first(pool, pools, pipe, item)

    def _cow(self, pool, group, slot: int, table_idx: int,
             rid: int | None = None) -> None:
        """Engine-side copy-on-write: splits the page and keeps the group's
        release bookkeeping in step with the table (kvpool owns the device
        copy — ONE compiled program per slab shape)."""
        old = int(group.tables[slot, table_idx])
        if pool.ensure_writable(group.tables[slot], table_idx):
            rp = group.row_pages[slot]
            rp[table_idx] = int(group.tables[slot, table_idx])
            if group.shared_pages[slot] > 0:
                group.shared_pages[slot] -= 1
            self.metrics.record_page_event(
                "cow", rid=rid, pages=1, used=pool.used_count(),
                total=pool.capacity)
            self.flight.record("cow", slot=slot, page=old,
                               fresh=rp[table_idx],
                               pages_used=pool.used_count())

    def _step_paged(self, pool, pools, pipe) -> None:
        """Retire expired resident rows, then DISPATCH one decode step over
        the live rows of EVERY bucket, packed into one call of the one
        decode program (``max_batch`` rows, the widest bucket's table): the
        weights are read once an iteration, whatever the number of buckets
        that hold rows. Only then land what was in flight before it: the
        previous call's tokens (``serve.decode.sync`` of call t follows
        ``serve.decode.dispatch`` of call t+1), then the first tokens of
        this iteration's final chunks — the chip runs the new call
        meanwhile — and free the slots of the rows that end with the new
        call (:meth:`_leave_paged`). Where nothing was dispatched (every row in flight ends
        with its step, nothing else is live) what is in flight lands at
        once.

        Where the buckets together hold more live rows than a call has
        (each may hold ``max_batch``), the step is ``ceil(live /
        max_batch)`` calls of the same program, and the rule holds call by
        call: each is dispatched, then the one before it lands, so a call
        is fed from the device by its immediate predecessor alone and every
        other row's token is on the host by then. A row that is not live
        (free, or still prefilling) is in no call: the rows a call does not
        fill run the masked-harmless dummy against page 0, so a prefilling
        neighbor's pages are never scribbled."""
        with annotate("serve.decode") as span:
            calls = self._pack_paged(pool, pools)
            steps: dict = {}  # bucket -> [rows, seconds] landed, unannounced
            dispatched = 0
            for n, call in enumerate(calls):
                # the buckets whose last rows this call carries: their step
                # records are due at its landing (a bucket's rows go on
                # into the next call where a call is full)
                later = {group.bucket for c in calls[n + 1:]
                         for group, _ in c}
                due = [group.bucket for group, _ in call
                       if group.bucket not in later]
                with annotate("serve.decode.dispatch",
                              bucket=_call_tag(call)) as dispatch:
                    launch = self._dispatch_paged(pool, pools, call,
                                                  dispatch, pipe, steps, due)
                if launch is None:
                    # nothing went out: what these buckets landed in this
                    # step's earlier calls is announced with the last of
                    # them (in flight), or now
                    if pipe.call is not None and pipe.call.steps is steps:
                        pipe.call.due.extend(due)
                    else:
                        self._record_steps(pool, steps, due)
                    continue
                dispatched += 1
                # (the first tokens land here too: their chunks ran before
                # the call just dispatched, and a row of them in a LATER
                # call of this step is fed from the host)
                self._drain_paged(pool, pools, pipe, then=launch)
                self._leave_paged(pool, pools, launch)
            if not dispatched:
                self._drain_paged(pool, pools, pipe)
            span.set_metadata(
                buckets=len({group.bucket for call in calls
                             for group, _ in call}),
                dispatches=dispatched)
        self._live_rows = sum(len(g.live_slots()) for g in pools.values())

    def _pack_paged(self, pool, pools) -> list:
        """The deadline sweep over every LM group, then this iteration's
        decode calls: the live rows of all groups that have a step left to
        run (the cursors count what is dispatched: a row whose last step is
        in flight only waits for its landing), in bucket order, cut into
        runs of at most ``max_batch``. A call is a list of ``(group,
        slots)``; a bucket whose rows do not fit the call they begin in
        goes on in the next."""
        now = self._clock()
        calls, room = [], 0
        # (a ProgramRowSet is the program lane's: _step_program_rows)
        for bucket in sorted(b for b, g in pools.items()
                             if not isinstance(g, ProgramRowSet)):
            group = pools[bucket]
            for i in group.occupied_slots():
                dl = group.entries[i].request.deadline
                if dl is not None and dl <= now:
                    self._retire_row_paged(
                        pool, pools, bucket, i, STATUS_EXPIRED, now,
                        reason=f"deadline {dl} passed mid-decode "
                               f"(now {now})")
            live = [i for i in group.live_slots()
                    if group.steps_done[i] < group.entries[i].request.steps]
            while live:
                if not room:
                    calls.append([])
                    room = self.max_batch
                take, live = live[:room], live[room:]
                calls[-1].append((group, take))
                room -= len(take)
        return calls

    def _dispatch_paged(self, pool, pools, call, span, pipe, steps: dict,
                        due):
        """One call's half of :meth:`_step_paged` before its landing: the
        copy-on-write gate, the packed decode inputs
        (:func:`.kvpool.decode_inputs`), the async ``lm_decode_paged``
        call, fed from the device (``pipe.feed``) for every row whose token
        the host has not seen, and then the host's bookkeeping for the step:
        ``positions`` and ``steps_done`` advance NOW, the call's tokens
        become the feed and each row is pointed at its entry. Returns the
        :class:`_Launch` the landing needs, or None where nothing was
        dispatched. ``span`` (``serve.decode.dispatch``) gets ``seq`` (the
        call's place among the programs dispatched, which its landing's
        span names too), ``ahead`` (1 where an earlier decode call had not
        landed at this dispatch: the step was pipelined) and the work the
        call was given beside the work that is useful, over all the rows it
        carries: ``padded_rows`` x ``table_width`` pages against
        ``kv_tokens``, the positions the live rows attend (each row's cache
        plus the entry this step writes) and ``kv_pages``, the pages that
        hold them (the grid steps of the decode kernel that compute; a
        spec's dispatch has them per attention kind); and ``sampled_rows``,
        the live rows with a temperature above 0 — at 0 the program took
        its argmax branch and never sorted the vocabulary."""
        from ..models.transformer import lm_decode_paged

        # an earlier landing may have retired a row packed for this call
        # (its eos, a failed call's rows), or dropped the pool with every
        # resident row (_drop_paged_pool)
        call = [(group, kept) for group, slots in call
                if (kept := [i for i in slots
                             if group.entries[i] is not None])]
        carried = _call_rows(call)
        rows = len(carried)
        span.set_metadata(rows=rows, ahead=int(pipe.call is not None))
        if not rows:
            return None
        try:
            for group, i in carried:
                # COW gate on each row's write page
                self._cow(pool, group, slot=i,
                          # analyze: ignore[host-sync] — host numpy
                          # block-table bookkeeping, not device data
                          table_idx=int(group.positions[i])
                          // self._page_len,
                          rid=group.entries[i].request.rid)
            faults.fire("serve.decode_step", path=f"bucket-{_call_tag(call)}")
            t0 = time.perf_counter()
            (tables, positions, cur, steps_done, seeds, temperature, top_p,
             top_k, prev_index) = decode_inputs(
                 call, self.max_batch, self._decode_pages, self._ring,
                 pipe.serial, stateful=bool(self._state_slots))
            out = lm_decode_paged(
                self.params, pool.pages, tables, positions, cur, steps_done,
                seeds, temperature, top_p, top_k, heads=self.heads,
                page_len=self._page_len, compute_dtype=self.compute_dtype,
                moe=self.moe, kernel=self._decode_kernel,
                prev_tokens=pipe.feed, prev_index=prev_index)
            pages, nxt, counts = _program_result(out)
        except Exception as exc:
            self._fail_paged_call(
                pool, pools, [(group, i, group.entries[i])
                              for group, i in carried], exc)
            return None
        pool.pages = pages
        # the step is the device's now: the cursors move on, the call's
        # tokens are the feed, and its entries past the live rows, and those
        # of rows that end with this step, are free for first tokens
        pipe.feed = nxt
        pipe.serial += 1
        pipe.free = list(range(rows, pipe.width))
        row = 0
        for group, slots in call:
            group.positions[slots] += 1
            group.steps_done[slots] += 1
            group.fed_serial[slots] = pipe.serial
            group.fed_index[slots] = np.arange(row, row + len(slots))
            pipe.free += [row + k for k, i in enumerate(slots)
                          if group.steps_done[i]
                          >= group.entries[i].request.steps]
            row += len(slots)
        if self._spec is not None:
            # the pages each attention kind's kernel is given a row
            tables, ring = tables[:2]
            span.set_metadata(global_table_width=tables.shape[1],
                              window_table_width=ring.shape[1])
            if self._state_slots:
                # the live rows whose state slot this call reads and writes
                span.set_metadata(state_rows=rows)
        if span.is_enabled():
            # the pages that hold what the live rows attend this step (the
            # decode kernel's grid steps that compute, of the padded_rows x
            # table width it is given): every position so far; for a
            # spec's sliding layers, the window's
            at = positions[:rows] // self._page_len
            if self._spec is None:
                # analyze: ignore[host-sync] — host numpy bookkeeping
                span.set_metadata(kv_pages=int((at + 1).sum()))
            elif self._spec.latent is not None:
                span.set_metadata(
                    latent_table_width=tables.shape[1],
                    # analyze: ignore[host-sync] — host numpy bookkeeping
                    latent_kv_pages=int((at + 1).sum()))
            else:
                low = (np.maximum(positions[:rows]
                                  - self._spec.window + 1, 0)
                       // self._page_len)
                span.set_metadata(
                    # analyze: ignore[host-sync] — host numpy bookkeeping
                    global_kv_pages=int((at + 1).sum()),
                    window_kv_pages=int((at - low + 1).sum())
                    if self._ring else 0)
        seq = pipe.dispatched()
        span.set_metadata(seq=seq, padded_rows=self.max_batch,
                          table_width=tables.shape[1],
                          # analyze: ignore[host-sync] — host numpy
                          kv_tokens=int(positions.sum()) + rows,
                          sampled_rows=int((temperature > 0).sum()))
        return _Launch([(group, i, group.entries[i]) for group, i in carried],
                       _call_tag(call), t0, nxt, counts, steps, due, seq, {},
                       self._sparse_blocks(positions[:rows]))

    def _sparse_blocks(self, positions) -> tuple | None:
        """What a decode call's sparse-attention layers read of its live
        rows' contexts, from the rows' positions alone (the selection's
        SIZE is a function of the position; which blocks, the device's):
        ``(blocks attended, blocks the contexts hold, rows in the sparse
        regime)``, the blocks summed over rows, KV heads and sparse layers.
        A row at position ``p`` holds ``p // block + 1`` blocks and attends
        all of them below ``dense_len``, ``topk`` of them (all, where fewer
        exist) from it on. None for a model without such layers."""
        sp = None if self._spec is None else self._spec.sparse
        if sp is None:
            return None
        held = positions // sp.block + 1
        sparse = positions >= sp.dense_len
        each = self._spec.kv_heads * len(self._spec.layer_names("sparse"))
        # analyze: ignore[host-sync] — host numpy bookkeeping
        return (int(np.where(sparse, np.minimum(held, sp.topk), held).sum())
                * each, int(held.sum()) * each, int(sparse.sum()))

    def _moe_counts(self, span, counts) -> None:
        """A ModelSpec's programs return, after the tokens, what their
        expert layers counted (summed over layers) and, a prefill chunk of a
        model with sparse layers, after those the blocks its tiles of
        queries met and the blocks their tokens took
        (``hybrid._attend_sparse_chunk``). A model with a lightning indexer
        returns after the experts' three: a prefill chunk the queries that
        selected and the (query, key) pairs they scored (``dsa_queries``,
        ``dsa_pairs_scored``); a decode call the entries its live rows'
        lists attended, the tokens their contexts hold (both summed over
        rows and layers) and the rows past ``index_topk``
        (``dsa_tokens_attended``, ``dsa_tokens_held``, ``dsa_rows``). Set on
        the span in which the result landed — the dispatch's own span has
        closed by then, and a span's fields cannot be set afterwards. Read
        only while a trace is taken; the arrays have landed with the
        tokens."""
        if counts is not None and span.is_enabled():
            # analyze: ignore[host-sync] — a few ints that rode back with
            # the tokens the caller has just synced on
            assigned, local, touched, *more = (
                int(c) for c in np.asarray(counts))
            span.set_metadata(moe_assignments=assigned,
                              moe_local_assignments=local,
                              moe_experts_touched=touched)
            if more and self._spec.sparse is not None:
                met, taken = more
                span.set_metadata(sparse_blocks_met=met,
                                  sparse_blocks_taken=taken)
            elif len(more) == 2:   # an indexer's prefill chunk
                queries, pairs = more
                span.set_metadata(dsa_queries=queries,
                                  dsa_pairs_scored=pairs)
            elif more:             # an indexer's decode call
                attended, held, rows = more
                span.set_metadata(dsa_tokens_attended=attended,
                                  dsa_tokens_held=held, dsa_rows=rows)

    def _leave_paged(self, pool, pools, launch: _Launch) -> None:
        """Free the slots of the rows whose step budget ends with ``launch``,
        the call just dispatched and by now all that is in flight: the host
        knows they end without seeing the token, so the next claim refills
        their slots (and may reuse their pages: every later program is
        behind this call in the device's stream) and only the landing
        record is kept (:class:`_Leaving`). A row that ends by ``eos`` is
        found at its landing, as before."""
        for n, (group, i, e) in enumerate(launch.rows):
            if (group.entries[i] is e
                    and group.steps_done[i] >= e.request.steps):
                launch.leaving[n] = self._release_row_paged(
                    pool, pools, group.bucket, i)

    def _land_paged(self, pool, pools, pipe, launch: _Launch) -> None:
        """A call's half of :meth:`_step_paged` after its landing — the one
        place the worker waits for a decode program (``serve.decode.sync``),
        with the next call already on the device. Rows are matched by ENTRY:
        a row retired while the call was in flight (its ``eos`` found one
        step late, a deadline, a failed neighbour) has its token discarded,
        never handed to the slot's next occupant. Then, in
        ``serve.decode.retire`` (``retired``, ``discarded``): the landed
        rows and the call's seconds (landing to landing: the interval that
        belongs to this call alone) go to their buckets' step records,
        divided among the buckets by rows; the records that are due are
        announced; then the tokens reach the rows' streams, a row that
        ``eos`` or its step budget ends is retired, and a row that had left
        its slot is answered."""
        try:
            with annotate("serve.decode.sync", bucket=launch.tag,
                          seq=launch.seq) as sync:
                # analyze: ignore[host-sync] — THE one intentional sync per
                # decode call: the host must see the emitted tokens to
                # retire rows (the next call was launched first)
                toks = np.asarray(launch.nxt)  # sync
                self._moe_counts(sync, launch.counts)
                if launch.sparse is not None:
                    attended, held, sparse_rows = launch.sparse
                    sync.set_metadata(sparse_blocks_attended=attended,
                                      sparse_blocks_held=held,
                                      sparse_rows=sparse_rows)
                    self.metrics.record_sparse(attended, held, sparse_rows)
        except Exception as exc:
            self._fail_paged_call(pool, pools, launch.rows, exc,
                                  leaving=launch.leaving)
            self._record_steps(pool, launch.steps, launch.due)
            return
        t = time.perf_counter()
        seconds, pipe.landed_t = t - max(launch.t0, pipe.landed_t), t
        pipe.landed(launch.seq)
        with annotate("serve.decode.retire", bucket=launch.tag) as retire:
            landed = [(n, group, i)
                      for n, (group, i, e) in enumerate(launch.rows)
                      if n in launch.leaving or group.entries[i] is e]
            for _, group, _ in landed:
                got = launch.steps.setdefault(group.bucket, [0, 0.0])
                got[0] += 1
                got[1] += seconds / len(landed)
            self._record_steps(pool, launch.steps, launch.due)
            now = self._clock()
            retired = 0
            for n, group, i in landed:
                tok = int(toks[n])
                left = launch.leaving.pop(n, None)
                if left is not None:
                    left.emitted.append(tok)
                    self._finish_row_paged(left, STATUS_OK, now)
                    retired += 1
                    continue
                group.cur_tok[i] = tok
                group.emitted[i].append(tok)
                r = group.entries[i].request
                if ((r.eos is not None and tok == r.eos)
                        or len(group.emitted[i]) >= r.steps):
                    self._retire_row_paged(pool, pools, group.bucket, i,
                                           STATUS_OK, now)
                    retired += 1
            retire.set_metadata(retired=retired,
                                discarded=len(launch.rows) - len(landed))

    def _record_steps(self, pool, steps: dict, due) -> None:
        """Announce the ``due`` buckets' step records and drop them from
        ``steps``: ONE ``step`` record per bucket that had live rows an
        iteration, ``rows`` a token each — a caller that rebuilds a
        request's token times from the records of its bucket (the
        benchmark's sink) counts on it, so a bucket whose rows two calls
        carried is announced once, at the later landing. A bucket none of
        whose rows landed has no record."""
        for bucket in due:
            if bucket not in steps:
                continue
            rows, seconds = steps.pop(bucket)
            self.metrics.record_step(bucket, rows, self.max_batch, seconds)
            self.flight.record(
                "step", bucket=list(bucket), rows=rows, seconds=seconds,
                queue_depth=self._queue.count, compiles=_compile_count(),
                pages_used=pool.used_count())

    def _retire_row_paged(self, pool, pools, bucket, slot: int,
                          status: str, now: float, reason: str = "") -> None:
        """Retire one resident row: free its slot and pages and answer its
        request."""
        self._finish_row_paged(
            self._release_row_paged(pool, pools, bucket, slot), status, now,
            reason)

    def _release_row_paged(self, pool, pools, bucket, slot: int) -> _Leaving:
        """Free one paged row's slot — the ONLY path a resident row leaves
        a group by with a Result to come, so every terminal status releases
        the row's pages exactly once (here, via the pool refcount; its
        page-unit admission reservation in :meth:`_retire`, by whoever wins
        the handle). Returns what the Result is built from."""
        group = pools[bucket]
        e = group.entries[slot]
        left = _Leaving(e, group.emitted[slot], {
            "bucket": bucket, "slot": slot, "queue_s": e.queue_s,
            "ttft_s": group.ttft_s[slot],
            "pages": len(group.row_pages[slot] or []),
            "shared_pages": int(group.shared_pages[slot])})
        if pool is None:
            group.release(slot)
        else:
            pages = pool.release_row(group, slot)
            # inside the request's span: the free record must join the
            # request's trace whichever step retires it
            with obs_trace.use(e.trace):
                self.metrics.record_page_event(
                    "free", rid=e.request.rid, pages=len(pages),
                    used=pool.used_count(), total=pool.capacity)
            self._record_pages(pool)
        return left

    def _finish_row_paged(self, left: _Leaving, status: str, now: float,
                          reason: str = "") -> None:
        """Answer the request of a row that has left its slot."""
        e = left.entry
        metrics = {**left.metrics, "total_s": now - e.enq_t}
        if status == STATUS_OK:
            toks = np.concatenate([
                np.asarray(e.request.prompt, np.int32),
                np.asarray(left.emitted, np.int32)])
            result = Result(e.request.rid, STATUS_OK, tokens=toks,
                            metrics=metrics)
        else:
            result = Result(e.request.rid, status, reason=reason,
                            metrics=metrics)
        self._retire(e, result)

    def _paged_pool_lost(self, pool) -> bool:
        """True when the page slab is gone: a failed donated call consumed
        it (the backends that implement donation delete the inputs on
        dispatch), or it is the output of a program that failed on the
        device — or of one dispatched on such an output, which is what the
        slab is when a call fails with its successor in flight. Waits for
        what is in flight (a failure path). Injected faults raise before
        the call and never trip this."""
        if pool is None:
            return False
        leaf = pool.pages["l0"][0]
        deleted = getattr(leaf, "is_deleted", None)
        if deleted and deleted():
            return True
        try:
            leaf.block_until_ready()
        except Exception:
            return True
        return False

    def _drop_paged_pool(self, pool, pools, reason: str) -> None:
        """The calling generation's slab died under a failed donated
        call: every resident row in its EVERY bucket lost its cache —
        requeue each within its attempt budget (the page-unit reservation
        is carried), fail the rest, and drop the pool; the live worker
        rebinds a zeroed rebuild at its next iteration (the same contract
        as worker-crash recovery). A STALE generation reaching here
        clears only its own (already superseded) map — the engine-level
        pool reference is cleared only when it still names this pool."""
        now = self._clock()
        for bucket, group in list(pools.items()):
            if isinstance(group, ProgramRowSet):
                continue  # program rows hold no pages: they ride out a
                # slab loss untouched and answer on this same iteration
            for i in group.occupied_slots():
                e = group.entries[i]
                group.release(i)  # page bookkeeping dies with the pool
                if e.attempts_left():
                    self._requeue(e, reason)
                else:
                    self._retire(e, Result(
                        e.request.rid, STATUS_ERROR, reason=reason,
                        metrics={"bucket": bucket, "queue_s": e.queue_s,
                                 "total_s": now - e.enq_t}))
            pools.pop(bucket)
        if self._kvpool is pool:
            memledger.get_ledger().free(f"kvpool:{self._name}",
                                        strict=False)
            self._kvpool = None
            self.metrics.record_page_event("lost", used=0,
                                           total=self._num_pages - 1)
            self.metrics.record_pages(self._num_pages - 1, 0, 0)

    def _fail_paged_call(self, pool, pools, rows, exc: Exception,
                         leaving=None) -> None:
        """A paged decode call died — at its dispatch, or at its landing
        with the next call already dispatched. ``rows`` are the ``(group,
        slot, entry)`` it carried. With the slab usable (an injected fault
        raised before launch) only those rows fail/retry, in every bucket
        the call carried, each once (one the next call carries too is
        released here and discarded at that landing), and their pages free;
        a slab that is consumed or poisoned (the failed program's output,
        which the next call was dispatched on) escalates to
        :meth:`_drop_paged_pool`, once: the other call's landing finds the
        pool gone and returns. ``leaving`` are the rows of it that had left
        their slots (:meth:`_leave_paged`): in no group whatever became of
        the pool, they retry or fail here, by entry."""
        reason = f"decode step failed: {type(exc).__name__}: {exc}"
        left = []
        while leaving:
            left.append(leaving.popitem()[1])
        # (a pool that is not the engine's any more: an earlier failure
        # already escalated to _drop_paged_pool, and every resident row,
        # this call's too, was requeued/failed there — a second handling
        # pass would find nothing of it on the cleared pools map)
        carried = ([(group, i) for group, i, e in rows
                    if group.entries[i] is e]
                   if pool is self._kvpool else [])
        if not carried and not left:
            return  # every row it carried is gone already (the call before
            # it failed and took them): nothing of this call is left to fail
        if memledger.is_oom_error(exc):
            memledger.dump_oom_forensics(reason)
        self.flight.record("decode_fault",
                           bucket=[list(b) for b in dict.fromkeys(
                               group.bucket for group, _, _ in rows)],
                           rows=len(carried) + len(left), error=reason,
                           queue_depth=self._queue.count,
                           compiles=_compile_count(),
                           pages_used=pool.used_count() if pool else 0)
        now = self._clock()
        for row in left:
            if row.entry.attempts_left():
                self._requeue(row.entry, reason)
            else:
                self._finish_row_paged(row, STATUS_ERROR, now, reason)
        if carried and self._paged_pool_lost(pool):
            self._drop_paged_pool(pool, pools, reason)
        elif carried:
            for group, i in carried:
                e = group.entries[i]
                if e.attempts_left():
                    pool.release_row(group, i)
                    self._requeue(e, reason)
                else:
                    self._retire_row_paged(pool, pools, group.bucket, i,
                                           STATUS_ERROR, now, reason=reason)
            self._record_pages(pool)
        self._flight_dump("decode-step-failed")

    def _paged_prefill_failure(self, pool, pools, bucket, slot: int,
                               exc: Exception) -> None:
        """A prefill chunk died: the row being prefilled retries within
        its attempt budget (the chunk cursor restarts from its shared
        prefix on the retry — resumability is host state) or errors;
        co-resident rows survive unless the slab was consumed."""
        group = pools[bucket]
        e = group.entries[slot]
        reason = f"prefill failed: {type(exc).__name__}: {exc}"
        if memledger.is_oom_error(exc):
            memledger.dump_oom_forensics(reason)
        self.flight.record("prefill_fault", bucket=list(bucket),
                           rid=e.request.rid, error=reason,
                           queue_depth=self._queue.count,
                           compiles=_compile_count(),
                           pages_used=pool.used_count() if pool else 0)
        if self._paged_pool_lost(pool):
            self._drop_paged_pool(pool, pools,
                                  f"pool lost to a failed prefill: {reason}")
        else:
            now = self._clock()
            pool.release_row(group, slot)
            if e.attempts_left():
                self._requeue(e, reason)
            else:
                self._retire(e, Result(
                    e.request.rid, STATUS_ERROR, reason=reason,
                    metrics={"bucket": bucket, "queue_s": e.queue_s,
                             "total_s": now - e.enq_t}))
            self.metrics.record_page_event(
                "free", rid=e.request.rid, used=pool.used_count(),
                total=pool.capacity)
            self._record_pages(pool)
        self._flight_dump("prefill-failed")
