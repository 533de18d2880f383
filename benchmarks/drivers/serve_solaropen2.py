"""Driver of the ``serve_solaropen2`` cells: ``ServeEngine`` serving a
decoder of the ``solar_open2`` configuration family (a
:class:`marlin_tpu.models.hybrid.ModelSpec` whose layers are mostly KDA
mixers, a delta rule whose decay is a vector a head, with NO attention, one
gated NoPE GQA layer to every three of them, and in EVERY layer a held share
of the routed experts beside one shared expert) under generated requests,
the prefix cache on: KV pages for the GQA layers, one recurrent-state slot a
row for the others, and state SNAPSHOTS through which a prefix is shared
with the state at its end.

Configuration keys read: the published keys of the model's ``config.json``
(``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``gqa_layers``, ``linear_attn_config``, every
``kda_*`` key, ``use_rope``, ``use_gqa_gate``, ``moe_intermediate_size``,
``n_shared_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``first_k_dense_replace``, ``rms_norm_eps``,
``tie_word_embeddings``), of which ``num_hidden_layers``,
``n_routed_experts`` and ``vocab_size`` give what is held here;
``deployment_share`` (``experts_total``: the router's width,
``first_expert``: the first expert held); ``param_dtype``,
``compute_dtype``, ``kda_state_dtype``, ``kda_chunk_size``,
``kda_gate_rank``; ``engine`` (max_batch, buckets, page_len, num_pages,
state_slots, snapshot_slots, prefill_chunk, prefix_cache, decode_kernel);
``check``.

Everything that drives and measures is ``drivers/serve.py``'s, by import:
the token sink (with ``drivers/serve_olmohybrid.py``'s record of the pages
an admission shared), the traffic, the window, the samples, the end-to-end
numbers. This file builds the model and compares it with
``reference/serve_solaropen2.py``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.drivers import serve as base
from benchmarks.drivers.serve_mistral4 import STATISTICS, model_spec
from benchmarks.drivers.serve_olmohybrid import TokenSink
from benchmarks.reference import serve_solaropen2 as reference
from benchmarks.seeds import seed_key

measure = base.measure
reduce_samples = base.reduce_samples
attempted_failed = base.attempted_failed
end_to_end = base.end_to_end


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
    """As ``drivers/serve_lfm2.py`` samples and bounds it: the longest
    request the window finished and, beside it, a seeded draw from the
    finished requests that ENTERED FROM A SNAPSHOT (from all finished ones
    where there are too few); the reference (the recurrence token by token
    from an empty state, every held expert computed the plain way, no cache,
    chunk or snapshot) runs once over each WHOLE prompt with its served
    tokens. Each limit of ``check.limits`` holds one statistic
    (``serve_mistral4.STATISTICS``: the largest, the 99th percentile, the
    mean) of the gaps by which the served tokens' reference logits lie below
    the reference's best; all three are printed beside it. At least
    ``check.min_shared_requests`` of the sampled requests must have entered
    from a snapshot: a cache that shares nothing fails the cell."""
    check = run.config["check"]
    limits = check["limits"]
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
    shared = state["sink"].shared_pages
    entered = [r for r in rest if shared.get(r["rid"], 0) > 0]
    if len(entered) >= int(check["sample_requests"]) - 1:
        rest = entered
    k = min(int(check["sample_requests"]) - 1, len(rest))
    picked = [longest] + [rest[int(i)] for i in
                          rng.choice(len(rest), size=k, replace=False)]
    buckets = run.config["engine"]["buckets"]
    pad_to = max(b[0] + b[1] for b in buckets)
    max_out = max(b[1] for b in buckets)
    gaps, ctrl, agree = [], [], []
    for rec in picked:
        if len(rec["tokens"]) != rec["n_prompt"] + rec["steps"]:
            gaps.append(np.asarray([np.inf]))
            continue
        got = reference.served_gaps(state["params"], run.config,
                                    rec["tokens"], rec["n_prompt"], pad_to,
                                    max_out, control=run.control)
        gaps.append(got["gaps"])
        agree.append(got["argmax_agree"])
        if run.control:
            ctrl.append(got["control_gaps"])
    allg = np.concatenate(gaps)
    facts = {"requests": len(picked), "served_tokens": len(allg),
             "longest": len(longest["tokens"]),
             "argmax_agree": float(np.mean(agree)) if agree else 0.0,
             "gap_max": float(allg.max()),
             "gap_p99": float(np.percentile(allg, 99)),
             "gap_mean": float(allg.mean())}
    out = [{"name": name, "value": float(STATISTICS[name](allg)),
            "limit": limit, "ok": bool(STATISTICS[name](allg) < limit),
            **facts} for name, limit in limits.items()]
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
