"""From a profiler trace to the numbers the per-layer metrics read.

``load(path)`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX, into a :class:`Trace`: for every device its operation events
(the ``XLA Ops`` line: one event per HLO instruction that ran) and its program
events (the ``XLA Modules`` line: one event per jitted program that ran), all
on the profiler's clock in seconds. The arithmetic below works on plain lists
of ``(start, end)`` intervals, so the tests drive it without a trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import json
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

ASYNC_LINE = "Async XLA Ops"

#: HLO opcodes that move data between chips; the TPU compiler also hides one
#: inside a custom fusion that ``calls=%all-reduce-scatter`` and the like
_MOVES = (r"(all-reduce|all-gather|reduce-scatter|collective-permute|"
          r"all-to-all|collective-broadcast|send|recv)")
_COLLECTIVE = re.compile("^" + _MOVES)
_COLLECTIVE_FUSION = re.compile(r"calls=%" + _MOVES)
#: a device operation's name is its HLO text:
#: ``%fusion.4 = f32[16,128]{...} fusion(...), kind=kLoop, calls=...``
_HLO = re.compile(r"^(%?[\w.\-]+) = (.*?)([a-z][\w\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")
_MODULE = re.compile(r"^(?:jit_+)?(.*?)(?:_jit)?(?:\(\d+\))?$")


def parse_op(name: str) -> dict:
    """``short`` (``%fusion.4``), ``opcode`` (``fusion``), ``kind``
    (``kLoop`` or ""), ``shape`` (``f32[16,128]``) of an operation's name."""
    m = _HLO.match(name)
    if not m:
        return {"short": name.split(" ")[0], "opcode": name.split(".")[0]
                .lstrip("%"), "kind": "", "shape": ""}
    kind = _KIND.search(name)
    shape = re.sub(r"\{[^}]*\}", "", m.group(2)).strip()
    return {"short": m.group(1), "opcode": m.group(3),
            "kind": kind.group(1) if kind else "", "shape": shape[:48]}


def label(ev: "Event", module: str = "") -> str:
    """A short, stable name for the breakdown."""
    p = parse_op(ev.name)
    kind = f"[{p['kind']}]" if p["kind"] else ""
    where = f"{module}/" if module else ""
    return f"{where}{p['short']} {p['opcode']}{kind} {p['shape']}".strip()


def module_short(name: str) -> str:
    """``jit__lm_decode_paged_jit(110...)`` -> ``lm_decode_paged``."""
    return _MODULE.match(name).group(1).strip("_")


@dataclasses.dataclass
class Event:
    name: str
    start: float      # seconds on the profiler's clock
    end: float
    category: str = ""

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: list          # Event, the XLA Ops line
    modules: list      # Event, the XLA Modules line
    async_ops: list = dataclasses.field(default_factory=list)  # Async XLA Ops

    @functools.cached_property
    def busy(self) -> list:
        """Disjoint sorted intervals in which some operation ran."""
        return union(op_intervals(self))


@dataclasses.dataclass
class Trace:
    devices: list      # DeviceTrace, one per chip that ran something
    host: list         # Event: TraceAnnotations named "bench:*"


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` output directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, host_prefix: str = "bench:") -> Trace:
    """Read an ``.xplane.pb``. Device planes are those named
    ``/device:TPU:<n>``; on each, the ``XLA Ops`` and ``XLA Modules`` lines."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {OPS_LINE: [], MODULES_LINE: [], ASYNC_LINE: []}
            for line in plane.lines:
                into = lines.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    start = ev.start_ns * 1e-9
                    cat = ("" if line.name == MODULES_LINE
                           else parse_op(ev.name)["opcode"])
                    into.append(Event(ev.name, start,
                                      start + ev.duration_ns * 1e-9, cat))
            if lines[OPS_LINE] or lines[MODULES_LINE]:
                for evs in lines.values():
                    evs.sort(key=lambda e: e.start)
                devices.append(DeviceTrace(plane.name, lines[OPS_LINE],
                                           lines[MODULES_LINE],
                                           lines[ASYNC_LINE]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        start = ev.start_ns * 1e-9
                        host.append(Event(ev.name[len(host_prefix):], start,
                                          start + ev.duration_ns * 1e-9))
    devices.sort(key=lambda d: d.name)
    host.sort(key=lambda e: e.start)
    return Trace(devices, host)


def describe(path: str, top: int = 12) -> dict:
    """What a trace holds, for a first look by hand: every plane and line
    with its event count and, per line, the event names that took most time."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            total, count, sample = {}, 0, None
            for ev in line.events:
                count += 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns * 1e-9
                if sample is None:
                    sample = {"name": ev.name, "start_ns": ev.start_ns,
                              "stats": {k: str(v)[:80] for k, v in ev.stats}}
            names = sorted(total.items(), key=lambda kv: -kv[1])[:top]
            lines.append({"line": line.name, "events": count,
                          "top": names, "first": sample})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}


# ------------------------------------------------------- interval arithmetic


def union(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(intervals, cover) -> list:
    """The parts of ``intervals`` (disjoint, sorted) that no interval of
    ``cover`` (disjoint, sorted) overlaps."""
    out, j = [], 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            cs, ce = cover[k]
            if cs > cur:
                out.append((cur, cs))
            cur = max(cur, ce)
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def intersect(a, b) -> list:
    """The overlap of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The idle intervals of ``[lo, hi]`` that ``busy`` (disjoint, sorted)
    leaves."""
    return subtract([(lo, hi)], busy)


# ------------------------------------------------------------- device numbers


def is_collective(ev: Event) -> bool:
    """By opcode (``category``, or the name where a trace has none), or a
    fusion whose called computation is a collective."""
    return bool(_COLLECTIVE.match(ev.category)
                or _COLLECTIVE.match(ev.name.lstrip("%"))
                or _COLLECTIVE_FUSION.search(ev.name))


def op_intervals(dev: DeviceTrace, keep=None) -> list:
    return [(e.start, e.end) for e in dev.ops if keep is None or keep(e)]


def window_of(trace: Trace, name: str = "window"):
    """``(start, end)`` of the benchmark's own traced-window annotation, or
    the extent of all device events where the host line lacks it."""
    for ev in trace.host:
        if ev.name == name:
            return ev.start, ev.end
    starts = [e.start for d in trace.devices for e in d.ops]
    ends = [e.end for d in trace.devices for e in d.ops]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def busy_seconds(dev: DeviceTrace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which some operation ran on the device."""
    return total(clip(dev.busy, lo, hi))


def idle_share(dev: DeviceTrace, lo: float, hi: float) -> float:
    return 1.0 - busy_seconds(dev, lo, hi) / (hi - lo)


def worst_idle_pct(ctx: dict):
    """The ``device_idle_pct.*`` readers: the idle share, in percent, of the
    chip that idles most; nothing without a trace."""
    trace = ctx["trace"]
    if trace is None or not trace.devices:
        return None
    lo, hi = ctx["window"]
    return 100.0 * max(idle_share(d, lo, hi) for d in trace.devices)


def exposed_collective_seconds(dev: DeviceTrace, lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` in which a collective ran on the device and no
    compute operation did."""
    moving = [(e.start, e.end) for e in dev.ops + dev.async_ops
              if is_collective(e)]
    coll = clip(union(moving), lo, hi)
    comp = clip(union(op_intervals(dev, lambda e: not is_collective(e))),
                lo, hi)
    return total(subtract(coll, comp))


def seconds_by_name(events, lo: float, hi: float) -> dict:
    """Clipped seconds summed per event name."""
    out = {}
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out[e.name] = out.get(e.name, 0.0) + (t - s)
    return out


def module_events(dev: DeviceTrace, pattern: str, lo: float, hi: float) -> list:
    """Program events whose name matches ``pattern`` and that lie wholly
    inside ``[lo, hi]``."""
    rx = re.compile(pattern)
    return [e for e in dev.modules
            if rx.search(e.name) and e.start >= lo and e.end <= hi]


def busy_inside(dev: DeviceTrace, spans, lo: float, hi: float) -> float:
    """Seconds in which an operation ran on the device inside ``spans``
    (for example: inside the events of one program)."""
    return total(intersect(clip(dev.busy, lo, hi),
                           clip(union(spans), lo, hi)))


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the device operations that took most time,
    averaged over the chips, each named ``program/%op opcode[kind] shape``."""
    acc = {}
    for dev in trace.devices:
        starts = [m.start for m in dev.modules]
        for ev in dev.ops:
            s, t = max(ev.start, lo), min(ev.end, hi)
            if t <= s:
                continue
            i = bisect.bisect_right(starts, ev.start) - 1
            inside = i >= 0 and ev.start < dev.modules[i].end
            name = label(ev, module_short(dev.modules[i].name)
                         if inside else "")
            acc[name] = acc.get(name, 0.0) + (t - s) / len(trace.devices)
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def named_gaps(dev: DeviceTrace, host_spans, lo: float, hi: float,
               n: int = 10) -> list:
    """``[[name, seconds], ...]``: the idle time of ``dev`` in ``[lo, hi]``
    summed by what the host was doing, the longest first. A gap takes the
    name of the latest host span (``(name, start, end)``) that had started
    when the gap began; ``unattributed`` where none had."""
    spans = sorted(host_spans, key=lambda s: s[1])
    acc, j, current = {}, 0, "unattributed"
    for s, e in gaps(clip(dev.busy, lo, hi), lo, hi):
        while j < len(spans) and spans[j][1] <= s:
            current = spans[j][0]
            j += 1
        acc[current] = acc.get(current, 0.0) + (e - s)
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


if __name__ == "__main__":
    import sys

    print(json.dumps(describe(sys.argv[1]), indent=1))
