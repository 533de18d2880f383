"""Continuous-batching inference serving (docs/serving.md).

The ROADMAP north star is "heavy traffic from millions of users"; the
reference delegated all request scheduling to Spark (SURVEY.md §0). This
package is the TPU-native replacement front half: admission control
(request.py), shape bucketing + per-bucket claim queues (batcher.py), the
paged KV-cache pool with copy-on-write prefix sharing (kvpool.py), the
worker-loop engine with chunked prefill and a drain-safe lifecycle
(engine.py), serving
observability through the EventLog (metrics.py), supervised worker
recovery with a restart circuit breaker (supervisor.py), and a
multi-replica router with failover, drain-safe rolling restarts, and
elastic membership (router.py), driven by the SLO-burn fleet controller
(fleet.py — docs/robustness.md covers the resilience layer).

The spine is workload-pluggable (programs/): ``Request.program`` routes a
request to a registered :class:`~.programs.BucketProgram` — paged LM
decode is the first implementation, and ALS recommendation scoring,
incremental PageRank queries, and batched classification ship alongside
it, all sharing the same admission budget, bucketing, supervisor, and
router (docs/serving.md, "BucketProgram interface").

Quick start::

    from marlin_tpu.serving import Request, ServeEngine

    with ServeEngine(params, heads=lm.heads) as eng:
        eng.warmup()                              # compile once per bucket
        h = eng.submit(Request(prompt=[1, 2, 3], steps=16))
        tokens = h.result(timeout=60).tokens
"""

from .batcher import (  # noqa: F401
    BatchFormer,
    normalize_buckets,
    pick_bucket,
)
from .engine import ServeEngine  # noqa: F401
from .kvpool import (  # noqa: F401
    PagedGroup,
    PagedKVPool,
    PagePoolExhausted,
    auto_num_pages,
)
from .fleet import FleetController  # noqa: F401
from .metrics import ServeMetrics, percentile  # noqa: F401
from .programs import (  # noqa: F401
    PROGRAM_REGISTRY,
    ALSScoreProgram,
    BucketProgram,
    ClassifyProgram,
    PagedLMProgram,
    PageRankQueryProgram,
    ProgramRowSet,
    available_programs,
    register_program,
)
from .router import Router  # noqa: F401
from .supervisor import Supervisor  # noqa: F401
from .request import (  # noqa: F401
    STATUS_ERROR,
    STATUS_EXPIRED,
    STATUS_OK,
    STATUS_REJECTED,
    STATUS_SHUTTING_DOWN,
    AdmissionQueue,
    Request,
    Result,
    ResultHandle,
)
