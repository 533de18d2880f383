"""Driver of the ``serve_lfm2`` cells: ``ServeEngine`` serving a decoder of
the ``lfm2_moe`` configuration family (a
:class:`marlin_tpu.models.hybrid.ModelSpec` whose layers are mostly gated
SHORT CONVOLUTIONS with no attention, one full-attention layer of narrow
heads to every three of them, two leading dense SwiGLU layers and then a
whole router of small experts with no shared expert, a tied head) under
generated requests, the prefix cache on: KV pages for the full layers, one
state slot a row that holds only the convolutions' tails, and snapshots of
those tails through which a prefix is shared.

Configuration keys read: the published keys of the model's ``config.json``
(``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
``num_experts_per_tok``, ``num_dense_layers``, ``layer_types``,
``conv_L_cache``, ``conv_bias``, ``norm_eps``, ``rope_theta``,
``use_expert_bias``, ``norm_topk_prob``, ``routed_scaling_factor``), of
which ``num_hidden_layers`` gives what is held here and ``vocab_size`` and
``num_experts`` are whole; ``param_dtype``, ``compute_dtype``; ``engine``
(max_batch, buckets, page_len, num_pages, state_slots, snapshot_slots,
prefill_chunk, prefix_cache, decode_kernel); ``check``.

Everything that drives and measures is ``drivers/serve.py``'s, by import:
the token sink (with ``drivers/serve_olmohybrid.py``'s record of the pages
an admission shared), the traffic, the window, the samples, the end-to-end
numbers. This file builds the model and compares it with
``reference/serve_lfm2.py``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.drivers import serve as base
from benchmarks.drivers.serve_mistral4 import STATISTICS
from benchmarks.drivers.serve_olmohybrid import TokenSink
from benchmarks.reference import serve_lfm2 as reference
from benchmarks.seeds import seed_key

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
    """As ``drivers/serve_olmohybrid.py`` samples it: the longest request
    the window finished and, beside it, a seeded draw from the finished
    requests that ENTERED FROM A SNAPSHOT (from all finished ones where
    there are too few); the reference (no tail, no chunk, no cache, every
    expert computed the plain way) runs once over each WHOLE prompt with its
    served tokens. Each limit of ``check.limits`` holds one statistic
    (``serve_mistral4.STATISTICS``: the largest, the 99th percentile, the
    mean) of the gaps by which the served tokens' reference logits lie below
    the reference's best; all three are printed beside it. At least
    ``check.min_shared_requests`` of the sampled requests must have entered
    from a snapshot: a cache that shares nothing fails the cell."""
    check = run.config["check"]
    limits = check["limits"]
    sharing = bool(run.config["engine"]["prefix_cache"])
    engine = state.pop("engine")
    del engine  # the slabs go before the reference's activations come
    gc.collect()
    finished = samples["finished"]
    if not finished:
        return [{"name": name, "value": float("inf"), "limit": limit,
                 "ok": False, "why": "no request finished"}
                for name, limit in limits.items()]
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
    facts = {"requests": len(picked), "served_tokens": served,
             "longest": len(longest["tokens"]),
             "argmax_agree": float(np.mean(agree)) if agree else 0.0,
             "gap_max": float(allg.max()),
             "gap_p99": float(np.percentile(allg, 99)),
             "gap_mean": float(allg.mean())}
    out = []
    for name, limit in limits.items():
        value = float(STATISTICS[name](allg))
        out.append({"name": name, "value": value, "limit": limit,
                    "ok": bool(value < limit), **facts})
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
        for name, stat in STATISTICS.items():
            low = float(stat(allc))
            out.append({"name": "control_" + name, "value": low,
                        "limit": limits.get(name), "ok": True,
                        "would_pass": bool(name in limits
                                           and low < limits[name])})
    return out
