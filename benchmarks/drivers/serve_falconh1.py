"""Driver of the ``serve_falconh1`` cells: ``ServeEngine`` serving a decoder
of the ``falcon_h1`` configuration family (a
:class:`marlin_tpu.models.hybrid.ModelSpec` whose every layer holds full
attention and a state-space mixer side by side, a dense SwiGLU after them)
under generated requests: KV pages and one recurrent-state slot a row, in
one pool.

Configuration keys read: the published keys of the model's ``config.json``
(``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``rope_theta``, ``intermediate_size``,
``rms_norm_eps``, every ``mamba_*`` key, every ``*_multiplier`` /
``*_multipliers`` key), of which ``num_hidden_layers`` gives what is held
here and ``vocab_size`` is whole; ``param_dtype``, ``compute_dtype``,
``ssm_state_dtype``; ``engine`` (max_batch, buckets, page_len, num_pages,
state_slots, prefill_chunk, decode_kernel); ``check``.

Everything that drives and measures is ``drivers/serve.py``'s, by import:
the token sink, the traffic, the window, the samples, the end-to-end
numbers. This file builds the model and compares it with
``reference/serve_falconh1.py``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.drivers import serve as base
from benchmarks.reference import serve_falconh1 as reference
from benchmarks.seeds import seed_key

TokenSink = base.TokenSink
measure = base.measure
reduce_samples = base.reduce_samples
attempted_failed = base.attempted_failed
end_to_end = base.end_to_end


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
    recurrence token by token) runs once over each prompt with its served
    tokens, and the widest gap by which a served token's reference logit
    lies below the reference's best is held to ``check.limits``."""
    check = run.config["check"]
    limit = check["limits"]["served_logit_gap"]
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
    if run.control:
        allc = np.concatenate(ctrl)
        out.append({"name": "control_served_logit_gap",
                    "value": float(allc.max()), "limit": limit, "ok": True,
                    "would_pass": bool(allc.max() < limit),
                    "gap_p99": float(np.percentile(allc, 99)),
                    "gap_mean": float(allc.mean())})
    return out
