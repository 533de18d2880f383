"""Driver of the serving cells: ``ServeEngine`` under generated requests.

Configuration keys read: ``d_model``, ``n_heads``, ``n_layers``,
``expansion_ratio``, ``vocab_size``, ``param_dtype``, ``compute_dtype``,
``engine`` (max_batch, buckets, page_len, num_pages, prefill_chunk,
decode_kernel), ``check``.

The engine has no token stream: a caller gets its tokens when the request is
done. The one channel on which a token's arrival on the host is announced
earlier is the ``log=`` sink a caller hands the engine. The benchmark hands it
:class:`TokenSink` and stamps every announcement with its own clock: a
``prefill`` record with ``new_tokens == 1`` is request ``rid``'s first token;
a ``step`` record is one more token for each of the ``rows`` live requests of
that bucket.
"""

from __future__ import annotations

import bisect
import gc
import threading
import time

import numpy as np

from benchmarks import stats
from benchmarks.generators import requests as traffic_gen
from benchmarks.reference import serve as reference
from benchmarks.seeds import seed_key

_RESULT_TIMEOUT_S = 300.0


class TokenSink:
    """What ``ServeEngine(log=...)`` writes to. Keeps, on this process's
    ``time.perf_counter``: first tokens ``(t, rid, bucket)``, decode steps
    ``(t, bucket, rows)``, and the names of every other record."""

    def __init__(self):
        self.first = []
        self.steps = []
        self.other = {}

    def event(self, kind: str, **f) -> None:
        t = time.perf_counter()
        ev = f.get("ev")
        if ev == "step" and f.get("new_tokens"):
            self.steps.append((t, tuple(f["bucket"]), int(f["rows"])))
        elif ev == "prefill":
            if f.get("new_tokens"):
                self.first.append((t, f.get("rid"), tuple(f["bucket"])))
        else:
            self.other[ev] = self.other.get(ev, 0) + 1


def make_weights(cfg: dict, seed: int) -> dict:
    """The decoder's weights, on the device, in one jitted call from the
    seed, in the type they are served in (``cfg["weights"]`` has the law)."""
    import jax
    import jax.numpy as jnp

    d, layers = int(cfg["d_model"]), int(cfg["n_layers"])
    d_ff = int(cfg["expansion_ratio"]) * d
    vocab = int(cfg["vocab_size"])
    dt = jnp.dtype(cfg["param_dtype"])

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 1 + 6 * layers)
        nrm = lambda k, shape, std: jax.random.normal(k, shape, dt) * std  # noqa: E731
        p = {"emb": nrm(ks[0], (vocab, d), 0.02),
             "ln_f": jnp.ones((d,), dt)}
        for i in range(layers):
            k = ks[1 + 6 * i: 7 + 6 * i]
            s = d ** -0.5
            p[f"l{i}"] = {
                "wq": nrm(k[0], (d, d), s), "wk": nrm(k[1], (d, d), s),
                "wv": nrm(k[2], (d, d), s), "wo": nrm(k[3], (d, d), s),
                "w1": nrm(k[4], (d, d_ff), s),
                "w2": nrm(k[5], (d_ff, d), d_ff ** -0.5),
                "ln1": jnp.ones((d,), dt), "ln2": jnp.ones((d,), dt)}
        return p

    return make(seed_key(seed))


def setup(run, plan) -> dict:
    import jax

    from marlin_tpu.serving import ServeEngine

    cfg, eng_cfg = run.config, run.config["engine"]
    params = make_weights(cfg, run.seed)
    jax.block_until_ready(params)
    run.phase("weights")
    sink = TokenSink()
    engine = ServeEngine(
        params, int(cfg["n_heads"]),
        buckets=[tuple(b) for b in eng_cfg["buckets"]],
        max_batch=int(eng_cfg["max_batch"]),
        page_len=int(eng_cfg["page_len"]),
        num_pages=int(eng_cfg["num_pages"]),
        prefill_chunk=int(eng_cfg["prefill_chunk"]),
        decode_kernel=eng_cfg["decode_kernel"],
        compute_dtype=cfg["compute_dtype"], log=sink)
    engine.warmup()
    run.phase("engine_warmup")
    run.facts.update(max_batch=int(eng_cfg["max_batch"]),
                     buckets=eng_cfg["buckets"])
    return {"params": params, "engine": engine, "sink": sink,
            "heads": int(cfg["n_heads"])}


class _Traffic:
    """Callers and their records. One lock guards the stream and the list."""

    def __init__(self, run, engine, plan):
        from marlin_tpu.serving import Request

        self.run, self.engine, self.plan = run, engine, plan
        self.Request = Request
        self.stream = traffic_gen.stream(plan)
        self.lock = threading.Lock()
        self.stop = threading.Event()
        self.records = []   # dicts: rid, due, submitted, done, status, ...
        self.errors = []

    def _send(self, due: float | None = None) -> dict:
        with self.lock:
            index, toks, olen = next(self.stream)
        req = self.Request(prompt=toks, steps=int(olen),
                           temperature=self.plan["temperature"])
        rec = {"rid": req.rid, "index": index, "n_prompt": len(toks),
               "steps": int(olen), "due": due, "status": None}
        with self.run.span("submit"):
            rec["submitted"] = time.perf_counter()
            rec["handle"] = self.engine.submit(req)
        with self.lock:
            self.records.append(rec)
        return rec

    @staticmethod
    def _collect(rec) -> None:
        res = rec.pop("handle").result(timeout=_RESULT_TIMEOUT_S)
        rec["done"] = time.perf_counter()
        rec["status"] = res.status
        rec["reason"] = res.reason
        rec["tokens"] = res.tokens

    def closed_caller(self) -> None:
        try:
            while not self.stop.is_set():
                self._collect(self._send())
        except BaseException as exc:  # a caller that dies must be seen
            self.errors.append(repr(exc))

    def open_dispatcher(self, t_start: float, dues: list) -> None:
        """Open loop: submit each request when it is due, whether or not
        earlier ones have finished; results are collected afterwards."""
        try:
            for due in dues:
                wait = t_start + due - time.perf_counter()
                if wait > 0 and self.stop.wait(wait):
                    return
                if self.stop.is_set():
                    return
                self._send(due=t_start + due)
        except BaseException as exc:
            self.errors.append(repr(exc))


def measure(run, state, plan, seconds: float) -> dict:
    engine, sink = state["engine"], state["sink"]
    traffic = _Traffic(run, engine, plan)
    arrival = plan["arrival"]
    ramp = plan["ramp_s"]
    t_start = time.perf_counter()
    if arrival["kind"] == "closed":
        threads = [threading.Thread(target=traffic.closed_caller, daemon=True,
                                    name=f"bench-caller-{i}")
                   for i in range(int(arrival["callers"]))]
    else:
        dues = traffic_gen.due_times(plan, ramp + seconds)
        threads = [threading.Thread(target=traffic.open_dispatcher,
                                    args=(t_start, dues), daemon=True,
                                    name="bench-dispatcher")]
    for t in threads:
        t.start()
    time.sleep(ramp)  # traffic before the window: set-up
    run.phase("ramp")
    t0 = run.open_window()
    snap0 = engine.metrics.snapshot()
    time.sleep(seconds)
    t1 = run.close_window()
    snap1 = engine.metrics.snapshot()
    traffic.stop.set()
    for t in threads:
        t.join(_RESULT_TIMEOUT_S)
    with traffic.lock:
        records = list(traffic.records)
    for rec in records:  # open loop: nobody has waited for these yet
        if "handle" in rec:
            traffic._collect(rec)
    engine.close()
    alive = [t.name for t in threads if t.is_alive()]
    if alive or traffic.errors:
        raise RuntimeError(f"callers failed: alive={alive} "
                           f"errors={traffic.errors}")
    return reduce_samples(run, records, sink, t0, t1, snap0, snap1)


def reduce_samples(run, records, sink, t0, t1, snap0, snap1) -> dict:
    """From the callers' records and the sink's announcements to samples:
    each request's token arrival times, and what of them fell in the window."""
    steps_by_bucket = {}
    for t, bucket, rows in sink.steps:
        steps_by_bucket.setdefault(bucket, []).append(t)
    first = {rid: (t, bucket) for t, rid, bucket in sink.first}
    ttft_ms, gaps_ms, lateness_ms, finished = [], [], [], []
    for rec in records:
        ok = rec["status"] == "ok"
        n_out = (len(rec["tokens"]) - rec["n_prompt"]) if ok else 0
        rec["n_out"] = n_out
        if rec["rid"] not in first:
            continue
        tf, bucket = first[rec["rid"]]
        origin = rec["due"] if rec["due"] is not None else rec["submitted"]
        if rec["due"] is not None:
            lateness_ms.append((rec["submitted"] - rec["due"]) * 1e3)
        if t0 <= tf <= t1:
            ttft_ms.append((tf - origin) * 1e3)
        times = steps_by_bucket.get(bucket, [])
        i = bisect.bisect_right(times, tf)
        arrivals = [tf] + times[i:i + max(n_out - 1, 0)]
        rec["arrivals"] = arrivals
        for a, b in zip(arrivals, arrivals[1:]):
            if t0 <= b <= t1:
                gaps_ms.append((b - a) * 1e3)
        if ok and len(arrivals) == n_out and t0 <= arrivals[-1] <= t1:
            finished.append(rec)  # its last token arrived inside the window
    in_window = [s for s in sink.steps if t0 <= s[0] <= t1]
    firsts_in = sum(1 for t, _, _ in sink.first if t0 <= t <= t1)
    tokens = firsts_in + sum(rows for _, _, rows in in_window)
    run.counters.update(
        decode_steps=len(in_window),
        decode_rows=sum(rows for _, _, rows in in_window),
        first_tokens=firsts_in, tokens=tokens,
        engine_new_tokens=snap1["new_tokens"] - snap0["new_tokens"],
        engine_steps=snap1["steps"] - snap0["steps"],
        retries=snap1["retries"], errors=snap1["errors"],
        other_records=dict(sink.other))
    for name, t, bucket in ([("first_token", t, b) for t, _, b in sink.first]
                            + [("step", t, b) for t, b, _ in sink.steps]):
        if t0 <= t <= t1:
            run.mark(f"after_{name}_{bucket[0]}x{bucket[1]}", t)
    failed = [r for r in records if r["status"] != "ok"]
    return {"window_s": t1 - t0, "tokens": tokens, "ttft_ms": ttft_ms,
            "gaps_ms": gaps_ms, "lateness_ms": lateness_ms,
            "finished": finished, "attempted": len(records),
            "failed": len(failed) + snap1["retries"] + snap1["errors"],
            "failures": [(r["rid"], r["status"], r.get("reason", ""))
                         for r in failed[:5]]}


def attempted_failed(samples) -> tuple:
    return samples["attempted"], samples["failed"]


def end_to_end(run, samples) -> dict:
    run.say("serve_samples", requests_finished_in_window=len(samples["finished"]),
        ttft_samples=len(samples["ttft_ms"]), gap_samples=len(samples["gaps_ms"]),
        tokens_in_window=samples["tokens"], counters=run.counters,
        failures=samples["failures"],
        ttft_p50_ms=(stats.percentile(samples["ttft_ms"], 50)
                     if samples["ttft_ms"] else None),
        itl_p50_ms=(stats.percentile(samples["gaps_ms"], 50)
                    if samples["gaps_ms"] else None),
        generator_lateness_p95_ms=(stats.percentile(samples["lateness_ms"], 95)
                                   if samples["lateness_ms"] else 0.0))
    out = {"tokens_s": samples["tokens"] / samples["window_s"]}
    if samples["ttft_ms"]:
        out["ttft_p95_ms"] = stats.percentile(samples["ttft_ms"], 95)
    if samples["gaps_ms"]:
        out["itl_p95_ms"] = stats.percentile(samples["gaps_ms"], 95)
    return out


def verify(run, state, plan, samples) -> list:
    """A seeded sample of the requests the window finished, the longest among
    them: the reference runs once over each prompt with its served tokens,
    and the widest gap by which a served token's reference logit lies below
    the reference's best is held to the limit."""
    check = run.config["check"]
    limit = check["limits"]["served_logit_gap"]
    engine = state.pop("engine")
    del engine  # the slab goes before the reference's activations come
    gc.collect()
    finished = samples["finished"]
    if not finished:
        return [{"name": "served_logit_gap", "value": float("inf"),
                 "limit": limit, "ok": False, "why": "no request finished"}]
    rng = np.random.default_rng([run.seed, 11])
    longest = max(finished, key=lambda r: len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    k = min(int(check["sample_requests"]) - 1, len(rest))
    picked = [longest] + [rest[int(i)] for i in
                          rng.choice(len(rest), size=k, replace=False)]
    buckets = run.config["engine"]["buckets"]
    pad_to = max(b[0] + b[1] for b in buckets)
    max_out = max(b[1] for b in buckets)
    gaps, ctrl, agree, served = [], [], [], 0
    for rec in picked:
        exact = len(rec["tokens"]) == rec["n_prompt"] + rec["steps"]
        if not exact:
            gaps.append(np.asarray([np.inf]))
            continue
        got = reference.served_gaps(state["params"], state["heads"],
                                    rec["tokens"], rec["n_prompt"], pad_to,
                                    max_out, control=run.control)
        gaps.append(got["gaps"])
        agree.append(got["argmax_agree"])
        served += len(got["gaps"])
        if run.control:
            ctrl.append(got["control_gaps"])
    allg = np.concatenate(gaps)
    worst = float(allg.max())
    out = [{"name": "served_logit_gap", "value": worst, "limit": limit,
            "ok": bool(worst < limit), "requests": len(picked),
            "served_tokens": served,
            "gap_p99": float(np.percentile(allg, 99)),
            "gap_mean": float(allg.mean()),
            "argmax_agree": float(np.mean(agree)) if agree else 0.0}]
    if run.control:
        allc = np.concatenate(ctrl)
        out.append({"name": "control_served_logit_gap",
                    "value": float(allc.max()), "limit": limit, "ok": True,
                    "gap_p99": float(np.percentile(allc, 99)),
                    "gap_mean": float(allc.mean())})
    return out
