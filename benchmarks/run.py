#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by the names in ``BENCHMARK.json``:
``configs/<config>.json`` (sizes, and ``driver``), ``drivers/<driver>.py``,
``traffic/<mix>.json`` (parameters, and ``generator``),
``generators/<generator>.py``, ``reference/<driver>.py`` and, in a traced run,
``layer_metrics/<metric>.py``. See ``benchmarks/README.md``.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, when
traced, ``breakdown``. Earlier lines are JSON objects too, for the reader.
Without a TPU the run exits non-zero and prints no result.
"""

import time

T_PROCESS = time.perf_counter()  # "process start" of setup_s: before any heavy import

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: a traced run measures this long at most: traces are large and what comes
#: back from the chip is capped
TRACE_SECONDS = 8.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _finite(x):
    """JSON has no Infinity: a note line spells a non-finite number out."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    if isinstance(x, dict):
        return {k: _finite(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_finite(v) for v in x]
    return x


def say(kind: str, **fields) -> None:
    print(json.dumps({"note": kind, **_finite(fields)}), flush=True)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, by path: names may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{kind}/{name}.py is not among the "
                                f"benchmark's files")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Run:
    """What one run knows and records: the cell's files, the host spans and
    counters of the measured window, and the window itself."""

    def __init__(self, args, bench_path: str):
        self.bench = load_json(bench_path)
        base = os.path.dirname(os.path.abspath(bench_path))
        self.cell = by_name(self.bench["workloads"], args.workload, "workload")
        entry = by_name(self.bench["configs"], self.cell["config"], "config")
        self.config = load_json(base, entry["file"])
        self.traffic = load_json(base, self.bench["paths"][0], "traffic",
                                 self.cell["traffic"] + ".json")
        self.chips = int(self.cell["chips"])
        self.seed = int(args.seed)
        self.trace = bool(args.trace)
        self.rehearsal = bool(args.allow_cpu_rehearsal)
        self.control = bool(args.control)
        self.seconds = (min(float(args.seconds), TRACE_SECONDS)
                        if self.trace else float(args.seconds))
        self.spans = []      # (name, start, end) on time.perf_counter
        self.counters = {}   # what the driver counted in the window
        self.facts = {}      # sizes the per-layer readers need
        self.window = None   # (start, end) on time.perf_counter
        self.setup_s = None
        self.compiles_in_window = 0
        self.cache = {"hits": 0, "compiles": 0}
        self._in_window = False
        self._annotation = None
        self.trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        self.devices = None
        self.phases = []     # (name, seconds) of set-up, in order
        self._phase_t = T_PROCESS

    say = staticmethod(say)

    def phase(self, name: str) -> None:
        """Close one phase of set-up: its seconds go into the window note."""
        now = time.perf_counter()
        self.phases.append((name, now - self._phase_t))
        self._phase_t = now

    # ------------------------------------------------------------ host spans

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))

    def mark(self, name: str, t: float | None = None) -> None:
        t = time.perf_counter() if t is None else t
        self.spans.append((name, t, t))

    # ---------------------------------------------------------------- window

    def on_jax_event(self, event: str, *_, **__) -> None:
        if event == _COMPILE_EVENT:
            self.cache["compiles"] += 1
        elif event == _CACHE_HIT_EVENT:
            self.cache["hits"] += 1
        else:
            return
        if self._in_window:
            self.compiles_in_window += 1

    def open_window(self) -> float:
        """Set-up ends here. In a traced run the profiler starts first; the
        ``bench:window`` annotation ties this clock to the trace's."""
        import jax

        if self.trace and not self.rehearsal:
            self.start_trace()
            self._annotation = jax.profiler.TraceAnnotation("bench:window")
            self._annotation.__enter__()
        t0 = time.perf_counter()
        self.setup_s = t0 - T_PROCESS
        self._in_window = True
        self.window = (t0, None)
        return t0

    def close_window(self) -> float:
        t1 = time.perf_counter()
        self._in_window = False
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = None
            self.stop_trace()
        self.window = (self.window[0], t1)
        return t1

    def start_trace(self) -> None:
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def stop_trace(self) -> None:
        import jax

        jax.profiler.stop_trace()


def device_line(run: Run) -> dict:
    """The device as JAX reports it, and the peak on the fullest chip used."""
    import jax

    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in run.devices)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when that is
    set, else at the fixed ``<checkout>/.jax_cache`` (the same directory the
    program's own ``enable_compile_cache`` would pick)."""
    import jax

    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def layer_metrics(run: Run, trace, lo: float, hi: float, spans) -> dict:
    """Every per-layer metric of this cell whose reader finds something.
    ``lo``, ``hi`` and ``spans`` are on the trace's clock."""
    from benchmarks import costs

    kind = run.devices[0].device_kind
    ctx = {"trace": trace, "window": (lo, hi), "spans": spans,
           "counters": run.counters, "facts": run.facts,
           "config": run.config, "traffic": run.traffic, "chips": run.chips,
           "peaks": None if run.rehearsal else costs.load_peaks(kind)}
    out = {}
    for m in run.bench["per_layer"]:
        if not applies(m, run.cell["name"]):
            continue
        value = load_module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="another BENCHMARK.json (the tests' tiny cells)")
    ap.add_argument("--allow-cpu-rehearsal", action="store_true",
                    help="run the control flow on a CPU; no device metric "
                         "is printed")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also read the lower-precision control's numbers "
                         "(for setting a limit; never in a measured run)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the profiler's files under .bench_trace/")
    args = ap.parse_args(argv)
    run = Run(args, args.bench)

    import marlin_tpu  # noqa: F401 - a checkout without the program ends here

    cache_dir = enable_compile_cache()
    import jax

    jax.monitoring.register_event_duration_secs_listener(run.on_jax_event)
    jax.monitoring.register_event_listener(run.on_jax_event)
    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if devs[0].platform != "tpu" and not run.rehearsal:
        print(f"benchmarks/run.py needs a TPU; JAX found {found}",
              file=sys.stderr)
        return 3
    if len(devs) < run.chips:
        print(f"cell {run.cell['name']} needs {run.chips} chips; JAX found "
              f"{found}", file=sys.stderr)
        return 3
    run.devices = devs[:run.chips]
    run.phase("imports_and_device")
    say("start", cell=run.cell["name"], seed=run.seed, seconds=run.seconds,
        trace=run.trace, device=found, compile_cache=cache_dir)

    driver = load_module("drivers", run.config["driver"])
    generator = load_module("generators", run.traffic["generator"])
    plan = generator.plan(run.traffic, run.seed, run.config)
    say("traffic", **generator.describe(plan))

    state = driver.setup(run, plan)
    samples = driver.measure(run, state, plan, run.seconds)
    lo, hi = run.window
    say("window", setup_s=run.setup_s, setup_phases=dict(run.phases),
        window_s=hi - lo,
        compiles_in_window=run.compiles_in_window,
        compiled_in_process=run.cache["compiles"],
        compile_cache_hits=run.cache["hits"])
    if run.compiles_in_window:
        print(f"{run.compiles_in_window} program(s) compiled inside the "
              f"measured window", file=sys.stderr)
        return 4
    device = device_line(run)  # the program's peak: before the reference runs

    attempted, failed = driver.attempted_failed(samples)
    values = driver.end_to_end(run, samples)
    values["setup_s"] = run.setup_s
    t_verify = time.perf_counter()
    compared = driver.verify(run, state, plan, samples)
    say("verify", seconds=time.perf_counter() - t_verify)
    for c in compared:
        say("compared", **c)
    correct = bool(compared) and all(c["ok"] for c in compared) and failed == 0

    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if run.rehearsal:
        say("cpu_rehearsal_values_not_measurements", **values)
        traced = (layer_metrics(run, None, lo, hi, run.spans)
                  if run.trace else {})
        say("cpu_rehearsal_layer_values_not_measurements",
            **{k: v["value"] for k, v in traced.items()})
        result["metrics"] = {}
    elif run.trace:
        from benchmarks import trace_reduce

        trace = trace_reduce.load(trace_reduce.find_xplane(run.trace_dir))
        t_lo, t_hi = trace_reduce.window_of(trace)
        offset = t_lo - lo  # perf_counter -> the profiler's clock
        host = [(n, s + offset, e + offset) for n, s, e in run.spans]
        result["metrics"] = layer_metrics(run, trace, t_lo, t_hi, host)
        busy = [trace_reduce.busy_seconds(d, t_lo, t_hi)
                for d in trace.devices]
        if not busy:
            print("the trace holds no device operation", file=sys.stderr)
            return 6
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = t_hi - t_lo
        idlest = trace.devices[busy.index(min(busy))]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(trace, t_lo, t_hi),
            "idle_gaps": trace_reduce.named_gaps(idlest, host, t_lo, t_hi)}
        if not args.keep_trace:
            shutil.rmtree(run.trace_dir, ignore_errors=True)
    else:
        wanted = [m for m in run.bench["end_to_end"]
                  if applies(m, run.cell["name"])]
        missing = [m["name"] for m in wanted if m["name"] not in values]
        if missing:
            print(f"driver reported no {missing}", file=sys.stderr)
            return 5
        result["metrics"] = {m["name"]: {"value": float(values[m["name"]]),
                                         "unit": m["unit"]} for m in wanted}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
