"""Driver of the ``serve_olmohybrid`` cells: ``ServeEngine`` serving a
decoder of the ``olmo_hybrid`` configuration family (a
:class:`marlin_tpu.models.hybrid.ModelSpec` whose layers are mostly gated
delta-rule mixers with NO attention, one full-attention layer to every three
of them, a dense SwiGLU after either) under generated requests, the prefix
cache on: KV pages for the full layers, one recurrent-state slot a row for
the others, and state SNAPSHOTS through which a prefix is shared with the
state at its end.

Configuration keys read: the published keys of the model's ``config.json``
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``rms_norm_eps``, ``layer_types``, every ``linear_*``
key, ``rope_parameters``, ``attention_bias``, ``hidden_act``), of which
``num_hidden_layers`` gives what is held here and ``vocab_size`` is whole;
``head_dim``, ``param_dtype``, ``compute_dtype``, ``linear_state_dtype``,
``linear_chunk_size``; ``engine`` (max_batch, buckets, page_len, num_pages,
state_slots, snapshot_slots, prefill_chunk, prefix_cache, decode_kernel);
``check``.

Everything that drives and measures is ``drivers/serve.py``'s, by import:
the token sink, the traffic, the window, the samples, the end-to-end
numbers. This file builds the model and compares it with
``reference/serve_olmohybrid.py``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.drivers import serve as base
from benchmarks.reference import serve_olmohybrid as reference
from benchmarks.seeds import seed_key

measure = base.measure
reduce_samples = base.reduce_samples
attempted_failed = base.attempted_failed
end_to_end = base.end_to_end


class TokenSink(base.TokenSink):
    """``drivers/serve.py``'s sink, which also keeps, per request, the pages
    its admission took from the prefix cache (the ``page`` record of action
    ``alloc``): what says whether a request was answered off another
    request's pages and snapshot."""

    def __init__(self):
        super().__init__()
        self.shared_pages = {}

    def event(self, kind: str, **f) -> None:
        if f.get("ev") == "page" and f.get("action") == "alloc":
            self.shared_pages[f.get("rid")] = int(f.get("shared", 0))
        super().event(kind, **f)


def model_spec(cfg: dict):
    from marlin_tpu.models.hybrid import ModelSpec

    return ModelSpec.from_config(cfg)


def make_weights(cfg: dict, seed: int) -> dict:
    """The model's weights, on the device, from the seed, a layer at a
    time."""
    from marlin_tpu.models.hybrid import init_params

    return init_params(model_spec(cfg), seed_key(seed))


def setup(run, plan) -> dict:
    import jax

    from marlin_tpu.serving import ServeEngine

    cfg, eng_cfg = run.config, run.config["engine"]
    spec = model_spec(cfg)
    params = make_weights(cfg, run.seed)
    jax.block_until_ready(params)
    run.phase("weights")
    sink = TokenSink()
    engine = ServeEngine(
        params, spec,
        buckets=[tuple(b) for b in eng_cfg["buckets"]],
        max_batch=int(eng_cfg["max_batch"]),
        page_len=int(eng_cfg["page_len"]),
        num_pages=int(eng_cfg["num_pages"]),
        state_slots=int(eng_cfg["state_slots"]),
        snapshot_slots=int(eng_cfg["snapshot_slots"]),
        prefix_cache=bool(eng_cfg["prefix_cache"]),
        prefill_chunk=int(eng_cfg["prefill_chunk"]),
        decode_kernel=eng_cfg["decode_kernel"], log=sink)
    engine.warmup()
    run.phase("engine_warmup")
    run.facts.update(max_batch=int(eng_cfg["max_batch"]),
                     buckets=eng_cfg["buckets"])
    return {"params": params, "engine": engine, "sink": sink}


def verify(run, state, plan, samples) -> list:
    """As ``drivers/serve.py`` samples it: a seeded sample of the requests
    the window finished, the longest among them; the reference (its
    recurrence token by token, from an empty state, no cache) runs once over
    each WHOLE prompt with its served tokens, and the widest gap by which a
    served token's reference logit lies below the reference's best is held
    to ``check.limits``. A request that entered from another request's
    pages and snapshot so meets a pass that never saw either; with the
    prefix cache on, the requests beside the longest are drawn from those
    that ENTERED FROM A SNAPSHOT where the window finished enough of them
    (a window still finishes a few requests of the first wave, which
    prefilled their history themselves: drawn from all, 1 run in 11 sampled
    two of them; my chip runs, PR 42), and at least
    ``check.min_shared_requests`` of the sampled requests must have been
    such: a cache that shares nothing fails the cell."""
    check = run.config["check"]
    limit = check["limits"]["served_logit_gap"]
    sharing = bool(run.config["engine"]["prefix_cache"])
    engine = state.pop("engine")
    del engine  # the slabs go before the reference's activations come
    gc.collect()
    finished = samples["finished"]
    if not finished:
        return [{"name": "served_logit_gap", "value": float("inf"),
                 "limit": limit, "ok": False, "why": "no request finished"}]
    rng = np.random.default_rng([run.seed, 11])
    longest = max(finished, key=lambda r: len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    shared = state["sink"].shared_pages if sharing else {}
    entered = [r for r in rest if shared.get(r["rid"], 0) > 0]
    if len(entered) >= int(check["sample_requests"]) - 1:
        rest = entered
    k = min(int(check["sample_requests"]) - 1, len(rest))
    picked = [longest] + [rest[int(i)] for i in
                          rng.choice(len(rest), size=k, replace=False)]
    buckets = run.config["engine"]["buckets"]
    pad_to = max(b[0] + b[1] for b in buckets)
    max_out = max(b[1] for b in buckets)
    gaps, ctrl, agree, served = [], [], [], 0
    for rec in picked:
        if len(rec["tokens"]) != rec["n_prompt"] + rec["steps"]:
            gaps.append(np.asarray([np.inf]))
            continue
        got = reference.served_gaps(state["params"], run.config,
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
            "served_tokens": served, "longest": len(longest["tokens"]),
            "gap_p99": float(np.percentile(allg, 99)),
            "gap_mean": float(allg.mean()),
            "argmax_agree": float(np.mean(agree)) if agree else 0.0}]
    if sharing:
        hits = sum(shared.get(rec["rid"], 0) > 0 for rec in picked)
        need = min(int(check["min_shared_requests"]), len(picked))
        out.append({"name": "sampled_requests_shared", "value": hits,
                    "limit": need, "ok": bool(hits >= need),
                    "finished_shared": len(entered) + (
                        shared.get(longest["rid"], 0) > 0),
                    "finished": len(finished)})
    if run.control:
        allc = np.concatenate(ctrl)
        out.append({"name": "control_served_logit_gap",
                    "value": float(allc.max()), "limit": limit, "ok": True,
                    "would_pass": bool(allc.max() < limit),
                    "gap_p99": float(np.percentile(allc, 99)),
                    "gap_mean": float(allc.mean())})
    return out
