"""Global configuration for marlin_tpu.

The reference spreads its knobs over three channels (SURVEY.md §5.6): CLI args,
SparkConf keys (``marlin.lu.basesize``/``marlin.cholesky.basesize``/
``marlin.inverse.basesize``, /root/reference matrix/DenseVecMatrix.scala:313,499,591)
and method parameters with defaults (``broadcastThreshold`` MB,
DenseVecMatrix.scala:196-198; mode strings on factorizations 283,475,568).

Here all of that is one dataclass with a global instance and a context manager,
so library calls and CLI examples share the same knob surface.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Iterator

import jax.numpy as jnp


@dataclasses.dataclass
class MarlinConfig:
    # Factorization base block sizes (reference defaults: 1000).
    lu_base_size: int = 1000
    cholesky_base_size: int = 1000
    inverse_base_size: int = 1000
    # Size threshold (matrix dim) below which factorizations run single-device
    # ("breeze" mode in the reference, DenseVecMatrix.scala:289-298 uses n > 6000).
    local_fallback_dim: int = 6000
    # Broadcast-multiply threshold in MB (DenseVecMatrix.scala:196-198 default 300).
    broadcast_threshold_mb: float = 300.0
    # Default element dtype for matrices. The reference is float64-on-JVM; the
    # TPU-native default is float32 storage (bf16 compute happens inside the MXU
    # via the precision setting below).
    default_dtype: Any = jnp.float32
    # Precision for jnp.dot/matmul on the hot path: "default" lets the MXU use
    # bf16 passes; "highest" forces f32-accurate multiplies (used by tests).
    matmul_precision: str = "highest"
    # Number of logical cores/devices hint for the CARMA split heuristic when no
    # mesh is given (the reference reads spark.default.parallelism,
    # MTUtils.scala:496-502).
    default_parallelism: int | None = None
    # SVD mode thresholds (DenseVecMatrix.scala:1569-1588).
    svd_local_dim: int = 2000
    # Lanczos iterations multiplier for dist-eigs SVD.
    lanczos_max_iter_factor: int = 10
    # sparse x sparse: above this worst-case product count (nse_a * nse_b, the
    # buffer XLA's BCOO spsp contraction allocates) the multiply routes to the
    # host CSR kernel — the regime the reference always runs in (its CSC x CSC
    # kernel is a per-block CPU routine, Matrices.scala:129-152). Under
    # jax.jit the host kernel runs through jax.pure_callback and needs a
    # static out_nse bound (mult_sparse_sparse's kwarg); without one the
    # trace fails with an error naming it.
    spsp_device_max_products: int = 1 << 27
    # Pallas kernel mode: None = interpret everywhere but on real TPU (the
    # CPU test mesh runs the interpreter, the chip runs Mosaic). False forces
    # Mosaic lowering — used by AOT compile-only runs against a TPU topology
    # (utils/aot.py), where the default backend is CPU but the kernels must
    # really compile. True forces the interpreter even on chip (debugging).
    pallas_interpret: bool | None = None
    # Host-RAM ceiling (bytes) for the remote-shard download cache used by
    # io.checkpoint.load_sharded during resharding restores. A restore whose
    # target regions touch every saved shard file re-downloads past this bound
    # instead of holding the whole global array on the host.
    ckpt_cache_bytes: int = 1 << 30
    # Checkpoint retention: after each committed save, io.checkpoint.
    # save_checkpoint prunes all but the newest `ckpt_keep` generations
    # (0 = keep everything). ResilientLoop passes its own `keep` explicitly
    # (default 3 — the fall-back depth when the latest generation is corrupt).
    ckpt_keep: int = 0
    # --- streaming prefetch (parallel/prefetch.py) ---------------------------
    # Default for the async host→device prefetch pipeline behind the streamed
    # ops (streamed_matmul/streamed_gramian, OutOfCoreMatrix). False falls
    # back to the synchronous read→convert→upload loop on the caller's thread.
    prefetch_enabled: bool = True
    # Backpressure: at most this many chunks read-but-not-yet-consumed at
    # once (the bounded queue depth). 2 = classic double buffering: chunk i+1
    # is produced/transferred while the device computes on chunk i.
    prefetch_depth: int = 2
    # Producer threads. 1 suffices when the source read dominates; >1 overlaps
    # dtype conversion/compression of several chunks (reads stay serialized —
    # chunk sources are plain iterators).
    prefetch_workers: int = 1
    # In-flight HBM budget (bytes) for prefetched-but-unconsumed chunks; a
    # producer blocks before device_put when the budget is full (at least one
    # chunk is always allowed through). 0 = unbounded (depth alone bounds it).
    prefetch_hbm_budget_bytes: int = 2 << 30
    # --- native data plane (io/chunkstore.py) --------------------------------
    # Reader-pool threads per chunk-store read: the native mcs_read fans the
    # touched chunks (CRC validation + dtype conversion) over this many
    # std::threads, all outside the GIL. 1 = serial in-call reads.
    data_plane_threads: int = 4
    # Staging dtype chunk-store reads convert into natively (None = the
    # stored dtype). "bfloat16" makes chunks surface pre-compressed, so the
    # streamed ops' host-side transfer cast is a no-op and H2D bytes halve —
    # direct-bf16 staging off disk.
    data_plane_dtype: str | None = None
    # CRC32C-validate every touched chunk on read. Costs one pass over the
    # bytes (still far cheaper than parsing text); turn off only for
    # throughput experiments on trusted files.
    data_plane_verify: bool = True
    # --- serving engine (serving/) -------------------------------------------
    # Slot rows per dispatched batch. Every batch is padded to exactly this
    # width (free slots carry dummy rows), so the compiled program count is
    # bounded by the bucket set, not the traffic pattern.
    serve_max_batch: int = 8
    # A partial batch dispatches once its oldest request has waited this long
    # (ms, on the engine's injectable clock); a full batch dispatches
    # immediately. 0 = dispatch as soon as anything is pending.
    serve_max_wait_ms: float = 10.0
    # Admission bound on requests pending-or-in-flight; submissions beyond it
    # are rejected with a reason (backpressure, never blocking the caller).
    serve_queue_depth: int = 256
    # The static (padded_prompt, decode_steps) shape set. Each bucket costs
    # one compile per sampling variant; prompts/steps round UP to the
    # smallest fitting bucket (docs/serving.md has tuning guidance).
    serve_buckets: tuple = ((64, 32), (256, 64))
    # Padded batch widths for non-LM BucketPrograms (serving/programs/): a
    # one-shot program batch pads up to the smallest width that fits, so
    # compiles per program are bounded by this set x its bucket set. Sorted
    # and deduplicated at program construction.
    serve_program_batches: tuple = (8, 32)
    # Static top-k depths ALS and PageRank queries compile for; a request's
    # k rounds UP to the smallest fitting depth (results slice back down).
    # Depths beyond the resident model's item/node count are dropped.
    serve_program_topk: tuple = (8,)
    # The paged KV cache: the engine owns ONE device-resident page slab
    # (serve_num_pages x serve_page_len KV rows per layer) shared by every
    # bucket, rows hold block tables of pages, admission charges the
    # request's ACTUAL pages (models/planner.request_pages) instead of the
    # bucket worst case, full prompt pages are prefix-shared copy-on-write
    # across requests, and long prompts prefill in serve_prefill_chunk-token
    # chunks interleaved with decode steps.
    # Tokens per KV page. Keep it a multiple of 8 (sublane-aligned pages —
    # the decode gather stays on the fast path); larger pages cut block-
    # table overhead but waste more of the last page per request and share
    # prefixes at coarser granularity.
    serve_page_len: int = 16
    # Total pages in the pool (page 0 is a sacrificial dummy). 0 = auto:
    # enough for every bucket's full extent at full width plus slack, so a
    # full slot set always fits (kvpool.auto_num_pages).
    serve_num_pages: int = 0
    # Prefill at most this many prompt tokens per worker iteration (rounded
    # up to a whole number of pages); decode steps interleave between
    # chunks, bounding how long a long prompt can monopolize the worker —
    # the TTFT-under-load knob. Size it near the typical prompt length:
    # lower bounds co-tenant TTFT tighter but caps prefill (admission)
    # throughput at chunk-tokens per iteration — far below the bucket
    # ceiling it queues prompts faster than it can admit them.
    serve_prefill_chunk: int = 256
    # Copy-on-write prefix cache: completed full prompt pages are kept
    # (refcounted, LRU-evicted under pressure) keyed by a rolling hash of
    # their tokens, so a shared system prompt is prefilled once and reused.
    serve_prefix_cache: bool = True
    # Paged decode-attention backend: 'pallas' runs the fused
    # ops/paged_attention kernel (reads the page slab in place through the
    # block table — no gather-materialized context; page_len must be a
    # multiple of 8, the engine aligns it), 'gather' the reference
    # gather-then-attend path, 'auto' picks pallas on real TPU and gather
    # elsewhere (interpret-mode Pallas is for tests, not serving). Greedy
    # token streams are identical across backends.
    serve_decode_kernel: str = "auto"
    # --- serving resilience (serving/supervisor.py, serving/router.py) ------
    # Supervisor watchdog: a worker whose heartbeat is older than this many
    # real seconds while work is pending is declared stuck and recovered
    # (its generation is superseded; live rows requeue within their attempt
    # budget). 0 disables the stuck-worker check (crash detection stays on).
    serve_watchdog_s: float = 30.0
    # Restart circuit breaker: more than serve_restart_max worker restarts
    # inside a sliding serve_restart_window_s window opens the breaker — the
    # engine is failed permanently (queued work retired, no further
    # restarts) instead of crash-looping against a deterministic bug.
    serve_restart_max: int = 5
    serve_restart_window_s: float = 60.0
    # Exponential-backoff base delay between worker restarts (doubles per
    # restart in the current window, capped at 16x).
    serve_restart_backoff_s: float = 0.05
    # Default relative deadline (seconds from submit) applied to requests
    # that carry neither deadline nor deadline_s. None = no default (requests
    # without a deadline never expire).
    serve_default_deadline_s: float | None = None
    # Engine replicas a Router builds when none are passed explicitly.
    serve_replicas: int = 2
    # Prefix-affine routing: requests whose prompt shares a first full KV
    # page are rendezvous-hashed to the same ready replica, so a shared
    # system prompt hits one replica's prefix cache instead of spraying
    # misses across the fleet. Falls back to power-of-two-choices when the
    # prompt has no shareable page, fewer than two replicas are ready, or
    # the chosen replica fails the attempt. False = always power-of-two.
    serve_prefix_affinity: bool = True
    # How long a migration requester waits for the target worker to service
    # a freeze/adopt/cache-warm handoff before cancelling it: rows not yet
    # bound at the deadline fall back to the retry path (rows already bound
    # stay adopted — never both).
    serve_migrate_timeout_s: float = 30.0
    # Prefix-cache chains a rebuilt replica pulls from the warmest peer
    # after a rolling restart (hottest-first; best-effort — a failed warm
    # never fails the restart). 0 disables cache warming.
    serve_cache_warm_prefixes: int = 32
    # --- serving SLOs (obs/slo.py, obs/timeseries.py) ------------------------
    # Declarative service-level objectives, evaluated live per engine (and
    # merged fleet-wide by the router): a tuple of dicts
    # {"name", "metric", "target", "window_s"[, "op", "budget"]}, e.g.
    # ({"name": "ttft", "metric": "p99:marlin_serve_ttft_seconds",
    #   "target": 0.5, "window_s": 300},) — see obs/slo.py for the metric
    # grammar (pNN/mean/ratio/rate/gauge over time-series names). Empty
    # (the default) disables the SLO engine and the time-series store
    # entirely: zero hot-path cost.
    serve_slo: tuple = ()
    # Seconds between SLO evaluations — the rate limit on the tick the
    # serving worker loop and the /debug/slo endpoint drive (no dedicated
    # evaluation thread exists).
    serve_slo_eval_interval_s: float = 5.0
    # The reactive burn window: error rates over this trailing window feed
    # the fast burn rate that trips breaches (each objective's own
    # window_s smooths the headline compliance number).
    serve_slo_fast_window_s: float = 60.0
    # Fast-window burn-rate threshold that flips an objective to breached
    # (burn 1.0 = consuming the error budget exactly over the window).
    serve_slo_burn_fast: float = 10.0
    # Hysteresis: consecutive evaluations with the fast burn under half
    # the threshold before a breached objective clears (and admission
    # shedding releases).
    serve_slo_hysteresis: int = 2
    # Breached objectives drive graceful degradation: admission sheds the
    # lowest-priority / longest-deadline work (clean reject-with-reason,
    # never a drop) while the breach persists. False = observe-only.
    serve_slo_shed: bool = True
    # Deadline slack (seconds to deadline at submission) under which a
    # request counts as urgent and earns one tier of shed protection.
    serve_slo_shed_slack_s: float = 2.0
    # Time-series store geometry: maximum trailing window any SLO/query
    # can span, and the ring's bucket alignment (memory is bounded by
    # window/bucket buckets per series).
    serve_ts_window_s: float = 600.0
    serve_ts_bucket_s: float = 5.0
    # --- elastic fleet (serving/fleet.py) ------------------------------------
    # Fleet-size bounds the controller may scale within. The router itself
    # never enforces these (manual add/retire is the operator's call); the
    # controller refuses to scale past either bound.
    serve_fleet_min_replicas: int = 1
    serve_fleet_max_replicas: int = 8
    # Seconds between controller evaluations on its injectable clock —
    # ticks closer together than this are no-ops (same contract as the SLO
    # engine's eval interval).
    serve_fleet_eval_interval_s: float = 5.0
    # Fleet-merged fast-window burn rate at/above which an evaluation
    # counts toward scale-OUT (burn 1.0 = consuming the error budget
    # exactly over the window), and at/below which it counts toward
    # scale-IN (budget slack — capacity is going spare).
    serve_fleet_out_burn: float = 1.0
    serve_fleet_in_burn: float = 0.1
    # Consecutive hot (or slack) evaluations before the controller acts —
    # one noisy window must not resize the fleet.
    serve_fleet_hysteresis: int = 3
    # Seconds after any completed action during which the controller only
    # observes (streaks still accumulate); lets the last action's effect
    # reach the burn windows before the next decision.
    serve_fleet_cooldown_s: float = 30.0
    # Flap damping: a scale action in the OPPOSITE direction of the
    # previous one is suppressed inside this window — oscillating burn
    # thrashes streak counters, never the fleet.
    serve_fleet_flap_window_s: float = 120.0
    # REBALANCE trigger: the most loaded replica's queue depth must exceed
    # the fleet mean by this factor (and be nontrivial) before the
    # controller sheds part of its seen-prefix ownership.
    serve_fleet_rebalance_ratio: float = 3.0
    # Fraction of the hot replica's rendezvous weight a rebalance sheds
    # (its weight is multiplied by 1 - frac, floored at 0.05): weighted
    # HRW re-places exactly that share of its keys, nobody else's move.
    serve_fleet_shed_frac: float = 0.5
    # Single-flight action timeout: an action leg still running past this
    # many seconds is recorded as timed out and the controller degrades to
    # "do nothing" until the leg actually finishes (the migration paths
    # own their own timeouts, so nothing is ever dropped — the controller
    # just stops initiating).
    serve_fleet_action_timeout_s: float = 60.0
    # --- autotune persistence (parallel/autotune.py) -------------------------
    # Where the empirical multiply-strategy winners persist across processes.
    # None = ~/.cache/marlin_tpu/autotune.json; "" disables the disk layer
    # (in-process caching still works).
    autotune_cache_path: str | None = None
    # --- observability (obs/) ------------------------------------------------
    # Port for the Prometheus /metrics endpoint started by
    # obs.start_from_config(): None disables (the default), 0 binds an
    # ephemeral port (read it off the returned server), otherwise the fixed
    # port. Loopback-bound; exposition is read-only.
    obs_http_port: int | None = None
    # Size-based EventLog rotation: a write that would push the log file past
    # this many bytes rotates it first (path -> path.1 -> path.2; two
    # backups kept, the oldest dropped). 0 = unbounded — fine for bounded
    # runs, not for a long-running serve loop flushing per event. Per-log
    # override: EventLog(..., max_bytes=...).
    obs_log_max_bytes: int = 0
    # Where on-demand profiler captures (obs.perf.capture_profile, the
    # /debug/profile endpoint, SIGUSR2) and flight-recorder dumps land.
    # None = <tempdir>/marlin_tpu_captures. The directory rotates: captures
    # beyond obs_profile_cap_bytes total are pruned oldest-first.
    obs_profile_dir: str | None = None
    obs_profile_cap_bytes: int = 256 << 20
    # Step-time flight recorder ring length (obs.perf.FlightRecorder): the
    # last N per-iteration records kept in memory per recorder (serving
    # worker loop, prefetch producers), dumped to JSONL on worker faults /
    # engine close / GET /debug/flight.
    obs_flight_len: int = 256
    # Leak-detection patience (obs/memledger.py LeakDetector): a component
    # debited in the MemoryLedger whose backend-reported live bytes have
    # not dropped after this many reconciliation windows (one per metrics
    # scrape of the memledger collector) raises a kind="mem" leak event
    # and fires the SLO-style hooks. Backends without memory_stats (CPU)
    # never reconcile, so the detector is inert there.
    obs_mem_leak_windows: int = 3


_config = MarlinConfig()


def get_config() -> MarlinConfig:
    return _config


def set_config(**kwargs: Any) -> MarlinConfig:
    for k, v in kwargs.items():
        if not hasattr(_config, k):
            raise AttributeError(f"unknown marlin_tpu config key: {k}")
        setattr(_config, k, v)
    return _config


@contextlib.contextmanager
def config_context(**kwargs: Any) -> Iterator[MarlinConfig]:
    old = {k: getattr(_config, k) for k in kwargs}
    try:
        set_config(**kwargs)
        yield _config
    finally:
        set_config(**old)
