"""Driver of the ``serve_minicpmsala`` cells: ``ServeEngine`` serving a
decoder of the ``minicpm_sala`` configuration family (a
:class:`marlin_tpu.models.hybrid.ModelSpec` whose layers are mostly lightning
layers, linear attention with a constant decay and NO page, one
sparse-attention layer to every three of them, which attends a SELECTION of
the row's blocks chosen by a score over mean-pooled keys kept beside its
pages; a dense SwiGLU after either) under generated requests, the prefix
cache on: KV pages with their compressed keys for the sparse layers, one
recurrent-state slot a row for the others, and state SNAPSHOTS through which
a document is shared with the state at its end.

Configuration keys read: the published keys of the model's ``config.json``
(``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``intermediate_size``, ``rms_norm_eps``,
``mixer_types``, every ``lightning_*`` key, ``rope_theta``, ``scale_emb``,
``scale_depth``, ``mup_denominator``, ``dim_model_base``, the ``*_use_*``
switches), of which ``num_hidden_layers`` and ``mixer_types`` give what is
held here and ``vocab_size`` is whole; ``first_layer``, ``sparse_config``,
``param_dtype``, ``compute_dtype``, ``lightning_state_dtype``,
``lightning_chunk_size``; ``engine`` (max_batch, buckets, page_len,
num_pages, state_slots, snapshot_slots, prefill_chunk, prefix_cache,
decode_kernel); ``check``.

Everything that drives and measures is ``drivers/serve.py``'s, by import (the
token sink with ``drivers/serve_olmohybrid.py``'s record of the pages an
admission shared, the traffic, the window, the samples, the end-to-end
numbers, the model's construction). This file compares the model with
``reference/serve_minicpmsala.py``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.drivers import serve as base
from benchmarks.drivers import serve_olmohybrid
from benchmarks.drivers.serve_mistral4 import STATISTICS
from benchmarks.reference import serve_minicpmsala as reference

measure = base.measure
reduce_samples = base.reduce_samples
attempted_failed = base.attempted_failed
end_to_end = base.end_to_end
model_spec = serve_olmohybrid.model_spec
make_weights = serve_olmohybrid.make_weights
setup = serve_olmohybrid.setup


def verify(run, state, plan, samples) -> list:
    """As ``drivers/serve_lfm2.py`` compares: the longest request the window
    finished and, beside it, a seeded draw from the finished requests that
    ENTERED FROM A SNAPSHOT (from all finished ones where there are too
    few); the reference (its lightning recurrence token by token, its
    selection step by step for every query; no cache, no chunk, no
    compressed-key cache) runs once over each WHOLE prompt with its served
    tokens. Each limit of ``check.limits`` holds one statistic
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
