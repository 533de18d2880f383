"""Driver of the ``serve_mistral4`` cells: ``ServeEngine`` serving a
latent-attention decoder built from a published configuration of the
DeepSeek-V3 family (a :class:`marlin_tpu.models.hybrid.ModelSpec` with
``latent`` layers) under generated requests, the prefix cache on.

Configuration keys read: the published keys of the model's ``config.json``
(``hidden_size``, ``num_attention_heads``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``v_head_dim``, ``rope_interleave``, ``rope_parameters``,
``first_k_dense_replace``, ``intermediate_size``, ``moe_intermediate_size``,
``n_shared_experts``, ``num_experts_per_tok``, ``n_group``, ``topk_group``,
``norm_topk_prob``, ``routed_scaling_factor``, ``rms_norm_eps``), of which
``num_hidden_layers``, ``n_routed_experts`` and ``vocab_size`` give what is
held here; ``deployment_share`` (``experts_total``: the router's width,
``first_expert``: the first expert held); ``param_dtype``,
``compute_dtype``; ``engine`` (max_batch, buckets, page_len, num_pages,
prefill_chunk, decode_kernel); ``check``.

Everything that drives and measures is ``drivers/serve.py``'s, by import:
the token sink, the traffic, the window, the samples, the end-to-end
numbers. This file builds the model and compares it with
``reference/serve_mistral4.py``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.drivers import serve as base
from benchmarks.reference import serve_mistral4 as reference
from benchmarks.seeds import seed_key

TokenSink = base.TokenSink
measure = base.measure
reduce_samples = base.reduce_samples
attempted_failed = base.attempted_failed
end_to_end = base.end_to_end


def model_spec(cfg: dict):
    from marlin_tpu.models.hybrid import ModelSpec

    share = cfg["deployment_share"]
    return ModelSpec.from_config(cfg, experts_total=share["experts_total"],
                                 first_expert=share["first_expert"])


def make_weights(cfg: dict, seed: int) -> dict:
    """The model's weights, on the device, from the seed, a layer at a time
    (one jitted draw of every expert through float32 would not fit)."""
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
        prefill_chunk=int(eng_cfg["prefill_chunk"]),
        decode_kernel=eng_cfg["decode_kernel"], log=sink)
    engine.warmup()
    run.phase("engine_warmup")
    run.facts.update(max_batch=int(eng_cfg["max_batch"]),
                     buckets=eng_cfg["buckets"])
    return {"params": params, "engine": engine, "sink": sink}


#: what each limit of ``check.limits`` bounds: a statistic of the gaps by
#: which the served tokens' reference logits lie below the reference's best
STATISTICS = {
    "served_logit_gap": np.max,
    "served_logit_gap_p99": lambda g: np.percentile(g, 99),
    "served_logit_gap_mean": np.mean}


def verify(run, state, plan, samples) -> list:
    """As ``drivers/serve.py`` samples it: a seeded sample of the requests
    the window finished, the longest among them; the reference runs once
    over each prompt with its served tokens (its whole 16.4k-17.9k tokens:
    a request that was answered off another request's cached pages is
    compared with a full forward pass). Each limit of ``check.limits`` holds
    one statistic (:data:`STATISTICS`) of the gaps by which the served
    tokens' reference logits lie below the reference's best."""
    check = run.config["check"]
    limits = check["limits"]
    engine = state.pop("engine")
    del engine  # the slab goes before the reference's activations come
    gc.collect()
    finished = samples["finished"]
    if not finished:
        return [{"name": name, "value": float("inf"), "limit": limit,
                 "ok": False, "why": "no request finished"}
                for name, limit in limits.items()]
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
    facts = {"requests": len(picked), "served_tokens": served,
             "longest": len(longest["tokens"]),
             "gap_max": float(allg.max()),
             "argmax_agree": float(np.mean(agree)) if agree else 0.0}
    out = []
    for name, limit in limits.items():
        value = float(STATISTICS[name](allg))
        out.append({"name": name, "value": value, "limit": limit,
                    "ok": bool(value < limit), **facts})
        if run.control:
            low = float(STATISTICS[name](np.concatenate(ctrl)))
            out.append({"name": "control_" + name, "value": low,
                        "limit": limit, "ok": True,
                        "would_pass": bool(low < limit)})
    return out
