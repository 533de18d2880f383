"""layer: programs (``serving/engine.py``, ``matrix/dense.py``). Wall seconds
covered by the program's start-up spans that have no parent,
``startup.import`` aside: ``serve.engine.init`` and ``serve.warmup`` in a
serving cell (``serve.kvpool.init`` is inside the warm-up, or on the worker's
thread beside it when the worker made the pool first: an interval two spans
cover counts once), the ``matmul.first_dispatch`` spans in a matrix cell. The
three ``setup_*`` sums of compiling threads' seconds may pass it where
programs compile side by side. Also prints the ``startup`` note of the run:
every span, every program's row and the programs compiled outside any span
by name, stamps in seconds since the first line of ``import marlin_tpu``
(within milliseconds of the process start ``setup_s`` counts from). Needs no
trace. Source: program counter."""

import json


def _rel(t, t_zero):
    return None if t is None else round(t - t_zero, 4)


def read(ctx):
    try:
        from marlin_tpu.obs.collectors import startup_report
    except (ImportError, AttributeError):  # no record: the parent commit
        return None
    rep = startup_report()
    t_zero = min([s["t0"] for s in rep["spans"]
                  if s["name"] == "startup.import"] or [0.0])
    print(json.dumps({
        "note": "startup",
        "spans": [{"name": s["name"], "parent": s["parent"],
                   "t0": _rel(s["t0"], t_zero), "t1": _rel(s["t1"], t_zero),
                   **s["fields"]} for s in rep["spans"]],
        "programs": [{"fun_name": r["fun_name"],
                      "trace_s": round(r["trace_s"], 4),
                      "lower_s": round(r["lower_s"], 4),
                      "backend_s": round(r["backend_s"], 4),
                      "cache": r["cache"], "retrieval_s": r["retrieval_s"],
                      "t": _rel(r["t_backend"], t_zero),
                      "within": r["within"]} for r in rep["programs"]],
        "outside": {name: {**o, "t_first": _rel(o["t_first"], t_zero),
                           "t_last": _rel(o["t_last"], t_zero)}
                    for name, o in rep["outside"].items()},
        "totals": rep["totals"], "dropped": rep["dropped"]}), flush=True)
    covered, reach = 0.0, float("-inf")
    for t0, t1 in sorted((s["t0"], s["t1"]) for s in rep["spans"]
                         if s["parent"] is None and s["t1"] is not None
                         and s["name"] != "startup.import"):
        covered += max(0.0, t1 - max(t0, reach))
        reach = max(reach, t1)
    return covered
