"""What one execution of a program is made of, from a kept trace (reading
only; runs where the trace lies, on the chip's machine or here).

    python3 benchmarks/run.py --workload serve.olmohybrid-sessions24 \\
        --seed 1 --seconds 30 --trace 1 --keep-trace
    python3 tools/chunk_parts.py .bench_trace/serve.olmohybrid-sessions24 \\
        prefill

(both in one ``chiprun`` call: the trace does not come back). Arguments: an
``.xplane.pb`` or the directory that holds one, then regular expressions
over the traced programs' names (default ``prefill`` and ``decode``);
``--levels=N`` among them keeps ``N`` levels of a scope's path (default 4: a
scope inside a loop's ``while/body/closed_call`` needs 6).

For each pattern one JSON line (the executions inside the capture, their
median and mean device time, the sum of the parts) and two tables in
milliseconds AN EXECUTION: SELF time of every device operation inside those
executions (an operation that contains others, a while loop or a
conditional, is charged only what its children leave) summed by the named
scope it was traced under (``benchmarks/laguna_spans.op_scopes``; digits
folded to ``#``, at most four levels, ``(none)`` for no scope) and by
operation. This is the reading PERF.md sections 5 and 6 quote for a prefill
chunk's parts (``ctx_gather``, ``linear_attn``, ``attn_full``, ``ffn`` ...):
PR 43 found 15.5 ms of the Olmo-Hybrid chunk under no scope with it, PR 45
reads the same fetches at 1.00 ms under ``ctx_gather``. Seconds, not the 18
minutes ``benchmarks/engine_spans.py`` takes on a whole serving trace."""
import bisect
import collections
import json
import os
import re
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from benchmarks import trace_reduce as tr  # noqa: E402
from benchmarks.laguna_spans import op_scopes  # noqa: E402


def self_seconds(ops):
    """[(event, self seconds)]: children (events nested inside) taken out."""
    out, stack = [], []
    for ev in sorted(ops, key=lambda e: (e.start, -e.end)):
        while stack and (stack[-1][0].end <= ev.start
                         or ev.end > stack[-1][0].end + 1e-9):
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= ev.seconds
        stack.append([ev, ev.seconds])
    out.extend(tuple(s) for s in stack)
    return out


def bucket(scope: str, levels: int = 4) -> str:
    parts = [p for p in scope.split("/") if not p.startswith("jit(")]
    keep = [re.sub(r"\d+", "#", p) for p in parts[:-1]]
    return "/".join(keep[:levels]) or "(none)"


def main(argv):
    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    levels = [int(a.split("=")[1]) for a in argv if a.startswith("--levels=")]
    patterns = [a for a in argv[1:] if not a.startswith("--levels=")] \
        or ["prefill", "decode"]
    trace = tr.load(path)
    dev = trace.devices[0]
    scopes = op_scopes(path)
    starts = [m.start for m in dev.modules]
    for pat in patterns:
        mods = [m for m in dev.modules if re.search(pat, m.name)]
        if not mods:
            print(json.dumps({"pattern": pat, "executions": 0}))
            continue
        inside = set(id(m) for m in mods)
        ops = []
        for ev in dev.ops:
            i = bisect.bisect_right(starts, ev.start) - 1
            if (i >= 0 and id(dev.modules[i]) in inside
                    and ev.start < dev.modules[i].end):
                ops.append(ev)
        n = len(mods)
        by_op, by_scope = collections.Counter(), collections.Counter()
        for ev, s in self_seconds(ops):
            by_op[tr.label(ev) + "  @" + scopes.get(ev.name, "")[-70:]] += s
            by_scope[bucket(scopes.get(ev.name, ""), *levels)] += s
        names = collections.Counter(tr.module_short(m.name) for m in mods)
        print(json.dumps({
            "pattern": pat, "executions": n, "modules": names,
            "median_ms": statistics.median(m.seconds for m in mods) * 1e3,
            "mean_ms": sum(m.seconds for m in mods) / n * 1e3,
            "ops_self_ms_an_execution": sum(by_op.values()) / n * 1e3}))
        print("  by scope, ms an execution:")
        for k, v in by_scope.most_common(30):
            print(f"    {v / n * 1e3:8.3f}  {k}")
        print("  by operation, ms an execution:")
        for k, v in by_op.most_common(45):
            print(f"    {v / n * 1e3:8.3f}  {k[:230]}")


if __name__ == "__main__":
    main(sys.argv[1:])
