"""What the ``serve_laguna`` cells' per-layer readers share: the spans and
counters a :class:`~marlin_tpu.models.hybrid.ModelSpec` adds to the engine's
``marlin:serve.*`` spans (``docs/observability.md``), and the device
operations of its expert layer and its two attention kinds, found by name.

- ``serve.decode.dispatch`` carries ``global_table_width``,
  ``window_table_width`` and, for its live rows, ``global_kv_pages`` /
  ``window_kv_pages``: the pages that hold the positions a full / a sliding
  layer attends this step.
- ``serve.decode.sync`` and ``serve.prefill.sync`` (where the result lands)
  carry ``moe_assignments``, ``moe_local_assignments``,
  ``moe_experts_touched``, summed over the expert layers.
- on the device: the grouped expert matmuls are the ``gmm`` custom calls
  (``ragged-dot`` where XLA's own lowering runs them); the sliding layers' decode kernel is
  ``_paged_decode_attention_window_call``, the full layers'
  ``_paged_decode_attention_call``; every operation of the expert layer
  (router, grouped matmuls, combine, shared expert) was traced under the
  ``moe_experts`` scope, which its metadata's ``tf_op`` stat holds
  (:func:`op_scopes` reads it from the file: ``ProfileData`` shows an
  event's own stats only).

On a trace of a program without these (the parent commit) every function
here finds nothing and returns ``None``.
"""

from __future__ import annotations

import glob
import os
import re

from benchmarks import engine_spans, trace_reduce

DECODE = r"lm_decode_paged"
GROUPED_MATMUL = re.compile(r"^%?gmm(\.\d+)?$|ragged-dot(?!-metadata)")
GROUPED_MATMUL_HINT = ("gmm", "ragged-dot")
WINDOW_KERNEL = re.compile(r"paged_decode_attention_window_call")
GLOBAL_KERNEL = re.compile(r"paged_decode_attention_call")
KERNEL_HINT = ("paged_decode_attention",)
MOE_SCOPE = "moe_experts"


def landings(ctx: dict, name: str = "serve.decode.sync"):
    """The window's ``name`` spans that carry the expert layers' counts."""
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    got = [s for s in engine_spans.in_window(spans, name, *ctx["window"])
           if "moe_assignments" in s.fields]
    return got or None


def decode_dispatches(ctx: dict, field: str):
    """The window's decode dispatches that carry ``field``."""
    spans = engine_spans.for_ctx(ctx)
    if spans is None:
        return None
    got = [s for s in engine_spans.dispatches(spans, *ctx["window"])
           if field in s.fields]
    return got or None


def named_intervals(dev, pattern, hint: tuple) -> list:
    """``(start, end)`` of ``dev``'s operations whose own name (the
    ``%name`` before the ``=``) matches ``pattern``. ``hint``: substrings of
    which every such name holds one; an operation's full text is parsed only
    where one occurs, since a decode dispatch of this model leaves some 2700
    operations in the trace and a traced window 800,000."""
    return [(e.start, e.end) for e in dev.ops
            if any(h in e.name for h in hint)
            and pattern.search(trace_reduce.parse_op(e.name)["short"])]


def op_seconds(ctx: dict, pattern, hint: tuple,
               module: str | None = None) -> float:
    """Traced seconds, in the window, of the first chip's operations whose
    own name matches ``pattern`` (:func:`named_intervals`); with ``module``,
    only inside the events of the programs whose name matches it."""
    dev = ctx["trace"].devices[0]
    lo, hi = ctx["window"]
    got = trace_reduce.clip(
        trace_reduce.union(named_intervals(dev, pattern, hint)), lo, hi)
    if module is not None:
        inside = trace_reduce.union(
            (e.start, e.end)
            for e in trace_reduce.module_events(dev, module, lo, hi))
        got = trace_reduce.intersect(got, inside)
    return trace_reduce.total(got)


def xplane_path(ctx: dict, trace_root: str | None = None):
    """The ``.xplane.pb`` behind ``ctx`` (as ``engine_spans.capture_for``
    finds it: by its ``bench:window``)."""
    if ctx.get("trace") is None:
        return None
    lo, hi = ctx["window"]
    for cell_dir in sorted(glob.glob(os.path.join(
            trace_root or engine_spans.TRACE_ROOT, "*"))):
        try:
            path = trace_reduce.find_xplane(cell_dir)
        except FileNotFoundError:
            continue
        w = engine_spans.load(path)["window"]
        if w is not None and abs(w[0] - lo) <= 1e-6 and abs(w[1] - hi) <= 1e-6:
            return path
    return None


def _fields(buf: bytes):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, bytes for a length-delimited field; fixed-width fields are
    skipped. Enough of the wire format to read an ``.xplane.pb``'s
    operation metadata, which ``jax.profiler.ProfileData`` does not expose."""
    def varint(i):
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value, i

    i, n = 0, len(buf)
    while i < n:
        key, i = varint(i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = varint(i)
            yield field, value
        elif wire == 2:
            size, i = varint(i)
            yield field, buf[i:i + size]
            i += size
        else:
            i += 8 if wire == 1 else 4


def op_scopes(path: str, stat: str = "tf_op") -> dict:
    """``{operation name: its framework name}`` for the first chip's plane of
    an ``.xplane.pb``: the ``tf_op`` stat of each operation's metadata, which
    holds the ``jax.named_scope`` path the operation was traced under
    (``jit(...)/.../moe_experts/dot_general``). XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key 1,
    value 2); XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2."""
    with open(path, "rb") as f:
        space = f.read()
    planes = sorted(
        (dict(name=next((v for k, v in _fields(p) if k == 2), b""), buf=p)
         for k, p in _fields(space) if k == 1),
        key=lambda d: d["name"])
    for plane in planes:
        if not plane["name"].startswith(b"/device:TPU:"):
            continue
        stat_names, events = {}, []
        for k, v in _fields(plane["buf"]):
            if k not in (4, 5):
                continue
            entry = dict(_fields(v))  # map entry: key 1, value 2
            if k == 5:
                stat_names[entry[1]] = next(
                    (x for f, x in _fields(entry[2]) if f == 2), b"")
            else:
                events.append(entry[2])
        out = {}
        for meta in events:
            name, scope = b"", None
            for f, x in _fields(meta):
                if f == 2:
                    name = x
                elif f == 5:
                    st = list(_fields(x))
                    if stat_names.get(dict(st).get(1)) == stat.encode():
                        for sf, sx in st:
                            if sf == 5:
                                scope = sx
                            elif sf == 7:
                                scope = stat_names.get(sx, b"")
            if scope is not None:
                out[name.decode(errors="replace")] = scope.decode(
                    errors="replace")
        return out
    return {}


def scoped_intervals(ctx: dict, scope: str):
    """``(start, end)`` of the first chip's operations that were traced under
    the ``jax.named_scope`` ``scope`` (by :func:`op_scopes`); ``None`` where
    the capture is not found or names no operation so."""
    path = xplane_path(ctx)
    if path is None or not ctx["trace"].devices:
        return None
    marked = {name for name, where in op_scopes(path).items()
              if f"/{scope}/" in where or where.endswith("/" + scope)}
    got = [(e.start, e.end) for e in ctx["trace"].devices[0].ops
           if e.name in marked]
    return got or None
