#!/usr/bin/env python3
"""Record the chip trace that ``test_launches.py`` reads.

    chiprun --chips 1 -- python3 benchmarks/tests/record_launches.py [requests]

A one-layer model behind ``ServeEngine`` (every knob but the sizes at its
default, so the decode program holds the Pallas kernel), a dozen ragged
greedy requests queued on a paused engine, and the whole of their serving
under one profiler capture as ``benchmarks/run.py`` takes it
(``python_tracer_level`` 0, ``host_tracer_level`` 2). The capture holds both
program kinds, chunks that are not final among them, and a few tens of
dispatches in well under a second. Writes
``chiprun_out/serve_tiny.xplane.pb`` (:func:`slim`: without the programs' HLO
and the per-operation lines, which no join reads) and prints what
``benchmarks/launches.py`` makes of it; copy the file to
``benchmarks/tests/data/`` by hand (``requests``: only the first so many of
the schedule, for a smaller file). Needs a TPU: a CPU capture has no device
plane, so nothing to join.
"""

import glob
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

#: (prompt length, steps): prompts of one to five 32-token chunks
SCHEDULE = ((20, 6), (40, 5), (75, 6), (100, 4), (130, 6), (30, 3),
            (150, 5), (60, 4))


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _encode(fields) -> bytes:
    """``(field number, int or bytes)`` pairs back into a message."""
    return b"".join(
        _varint(k << 3) + _varint(v) if isinstance(v, int)
        else _varint(k << 3 | 2) + _varint(len(v)) + v for k, v in fields)


def slim(space: bytes) -> bytes:
    """The capture less what no join reads: the ``/host:metadata`` plane (the
    programs' HLO, 780 KB) and, on a device's plane, the per-operation lines
    (``XLA Ops``, ``Async XLA Ops``) with their event metadata (150 KB of
    HLO text). Every host thread and the ``XLA Modules`` line stay as
    recorded. XSpace.planes = 1; XPlane.name = 2, .lines = 3,
    .event_metadata = 4 (map: key 1); XLine.name = 2, .events = 4;
    XEvent.metadata_id = 1: none of these messages holds a fixed-width
    field, so ``laguna_spans._fields`` reads them whole."""
    from benchmarks.laguna_spans import _fields

    def plane(buf: bytes):
        fields = list(_fields(buf))
        name = next((v for k, v in fields if k == 2), b"")
        if name == b"/host:metadata":
            return None
        if not name.startswith(b"/device:TPU:"):
            return buf
        fields = [(k, v) for k, v in fields if k != 3 or next(
            x for f, x in _fields(v) if f == 2) not in (b"XLA Ops",
                                                        b"Async XLA Ops")]
        used = {next(x for f, x in _fields(ev) if f == 1)
                for k, v in fields if k == 3
                for g, ev in _fields(v) if g == 4}
        return _encode((k, v) for k, v in fields
                       if k != 4 or dict(_fields(v))[1] in used)

    return _encode((k, v if k != 1 else kept) for k, v in _fields(space)
                   if k != 1 or (kept := plane(v)) is not None)


def main(requests: int = len(SCHEDULE)) -> int:
    import jax

    from benchmarks import launches
    from marlin_tpu.models import TransformerLM
    from marlin_tpu.serving import STATUS_OK, Request, ServeEngine

    lm = TransformerLM(vocab=512, d_model=256, heads=2, layers=1, seed=9)
    eng = ServeEngine(lm.init_params(), lm.heads, buckets=((64, 8), (160, 8)),
                      max_batch=4, prefill_chunk=32, start=False)
    eng.warmup()
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=out) as capture:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(capture, profiler_options=opts)
        try:
            handles = eng.submit_many(
                [Request(prompt=list(range(1, 1 + n)), steps=steps)
                 for n, steps in SCHEDULE[:requests]])
            eng.start()
            results = [h.result(timeout=120) for h in handles]
        finally:
            jax.profiler.stop_trace()
            eng.close()
        found = glob.glob(os.path.join(capture, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        path = os.path.join(out, "serve_tiny.xplane.pb")
        with open(found[0], "rb") as f, open(path, "wb") as g:
            g.write(slim(f.read()))
    got = launches.describe(path)
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "ok": all(r.status == STATUS_OK for r in results),
                      "bytes": os.path.getsize(path),
                      **{k: v for k, v in got.items() if k != "launches"}}))
    for line in got.get("launches", ()):
        print(json.dumps(line))
    return 0 if jax.devices()[0].platform == "tpu" else 3


if __name__ == "__main__":
    sys.exit(main(*map(int, sys.argv[1:2])))
