"""Driver of the ``serve_deepseekv32`` cells: ``ServeEngine`` serving a
latent-attention decoder of the ``deepseek_v32`` configuration family (a
:class:`marlin_tpu.models.hybrid.ModelSpec` whose every layer has a lightning
indexer: a query attends the ``index_topk`` tokens its index scores rank
first, out of latent pages beside which the index keys ride in a second
page-indexed array; group-limited sigmoid routing over experts of which a
share is held) under generated requests, the prefix cache on.

Configuration keys read: the published keys of the model's ``config.json``
(``hidden_size``, ``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``index_n_heads``,
``index_head_dim``, ``index_topk``, ``rope_scaling``, ``rope_theta``,
``intermediate_size``, ``moe_intermediate_size``, ``n_shared_experts``,
``num_experts_per_tok``, ``n_group``, ``topk_group``, ``norm_topk_prob``,
``routed_scaling_factor``, ``rms_norm_eps``), of which ``num_hidden_layers``,
``first_k_dense_replace``, ``n_routed_experts`` and ``vocab_size`` give what
is held here; ``rope_interleave``, ``index_norm_eps`` (assumed);
``deployment_share`` (``experts_total``, ``first_expert``); ``param_dtype``,
``compute_dtype``; ``engine`` (max_batch, buckets, page_len, num_pages,
prefill_chunk, prefix_cache, decode_kernel); ``check`` (sample_requests,
min_shared_requests, reference_segment, limits, cached_index_key_gap).

Everything that drives and measures is ``drivers/serve.py``'s, by import (the
token sink with ``drivers/serve_olmohybrid.py``'s record of the pages an
admission shared, the traffic, the window, the samples, the end-to-end
numbers; the model's construction is ``drivers/serve_mistral4.py``'s). This
file puts the traffic's shared documents into the prefix cache before the
callers start (:func:`measure`) and compares the model with
``reference/serve_deepseekv32.py``.
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.drivers import serve as base
from benchmarks.drivers import serve_mistral4
from benchmarks.drivers.serve_olmohybrid import TokenSink
from benchmarks.generators import requests as traffic_gen
from benchmarks.reference import serve_deepseekv32 as reference

reduce_samples = base.reduce_samples
attempted_failed = base.attempted_failed
end_to_end = base.end_to_end
model_spec = serve_mistral4.model_spec
make_weights = serve_mistral4.make_weights
STATISTICS = serve_mistral4.STATISTICS


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
        prefix_cache=bool(eng_cfg["prefix_cache"]),
        prefill_chunk=int(eng_cfg["prefill_chunk"]),
        decode_kernel=eng_cfg["decode_kernel"], log=sink)
    engine.warmup()
    run.phase("engine_warmup")
    run.facts.update(max_batch=int(eng_cfg["max_batch"]),
                     buckets=eng_cfg["buckets"])
    return {"params": params, "engine": engine, "sink": sink}


def documents(plan) -> list:
    """The traffic's shared documents, as its own stream hands them out: the
    leading ``shared_prefix.length`` tokens of its prompts, each once."""
    sp = plan["shared_prefix"]
    if not sp:
        return []
    docs = {}
    for _, (_, toks, _) in zip(range(64 * int(sp["count"])),
                               traffic_gen.stream(plan)):
        doc = toks[:int(sp["length"])]
        docs.setdefault(doc.tobytes(), doc)
        if len(docs) == int(sp["count"]):
            break
    return list(docs.values())


def compile_reference(run, state) -> float:
    """Compile the reference's layer programs (a dense layer's and an expert
    layer's, at the shapes :func:`verify` will call them with) and return
    the seconds it took. Called while the engine's worker serves the
    documents and this thread would only wait: where the process has a
    compilation cache, :func:`verify` then finds them there and a run on a
    new machine is the shorter by their compile time. Nothing is kept, and a
    failure costs nothing but that."""
    import time

    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    cfg, params = run.config, state["params"]
    seg = int(cfg["check"].get("reference_segment", reference.SEGMENT))
    pad_to = max(b[0] + b[1] for b in cfg["engine"]["buckets"])
    m = reference.describe(cfg)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    keys = tuple(f32(-(-pad_to // seg) * seg, w)
                 for w in (m["kv_rank"], m["rope_dim"], m["ix_dim"]))
    try:
        with jax.default_matmul_precision("highest"):
            for i in sorted({0, min(m["dense_first"], m["n_layers"] - 1)}):
                reference.layer.lower(
                    f32(seg, params["emb"].shape[1]), params[f"l{i}"], keys,
                    jax.ShapeDtypeStruct((), jnp.int32),
                    dense=i < m["dense_first"], dims=reference._dims(cfg),
                    quant=reference._identity, flaw="",
                    gathered=True).compile()
    except Exception as exc:  # the comparison compiles them itself then
        run.say("reference_compile_failed", error=repr(exc))
    return time.perf_counter() - t0


def measure(run, state, plan, seconds: float) -> dict:
    """``drivers/serve.py``'s, behind one step of set-up: each shared
    document is served ONCE (the document and one token more, one step) and
    so published to the prefix cache, as a deployment holds the documents
    its callers ask about; the callers start when all are in (meanwhile
    this thread compiles the reference's programs, :func:`compile_reference`).
    A closed loop
    that started on an empty cache would ask for a private copy of a
    document for every row, which the pool does not hold (``engine.
    num_pages``), and the engine fails an admission whose pages it lacks."""
    from marlin_tpu.serving import Request

    engine = state["engine"]
    handles = [engine.submit(Request(
        prompt=np.concatenate([doc, doc[:1]]), steps=1,
        temperature=plan["temperature"])) for doc in documents(plan)]
    run.facts.update(reference_compile_s=compile_reference(run, state))
    for h in handles:
        res = h.result(timeout=base._RESULT_TIMEOUT_S)
        if res.status != "ok":
            raise RuntimeError(f"a document was not served: {res.status} "
                               f"{res.reason}")
    run.phase("documents")
    return base.measure(run, state, plan, seconds)


def cached_index_keys(engine, prompt) -> dict:
    """The index keys the engine's prefix cache holds for ``prompt``'s
    cached pages, read out of the pool's second array of every layer that
    has one: ``{layer: (cached positions, index_head_dim)}``."""
    pool = engine._kvpool
    _, pids = pool.match_prefix(np.asarray(prompt, np.int32))
    try:
        at = np.asarray(pids, np.int32)
        return {name: np.asarray(arrs[1][at]).reshape(-1, arrs[1].shape[-1])
                for name, arrs in pool.pages.items() if len(arrs) == 2}
    finally:
        pool.release(pids)


def index_key_gaps(held: dict, kept: list, page_len: int) -> np.ndarray:
    """A page and layer: the mean distance of the cache's index keys from
    the reference's over the reference's mean size there (a page whose keys
    were never written reads 1, another document's about 1.4)."""
    out = []
    for name, ref in zip(sorted(held, key=lambda s: int(s[1:])), kept):
        n = min(len(held[name]), len(ref)) // page_len * page_len
        got = held[name][:n].astype(np.float32).reshape(-1, page_len,
                                                        ref.shape[-1])
        want = ref[:n].reshape(got.shape)
        out.append(np.abs(got - want).mean(axis=(1, 2))
                   / np.abs(want).mean(axis=(1, 2)))
    return np.concatenate(out) if out else np.zeros(0)


def pick(run, finished, shared) -> list:
    """The requests the reference meets: the longest the window finished
    and, beside it, a seeded draw from the finished requests that ENTERED
    FROM CACHED PAGES, those that begin with the longest one's document
    first (the reference computes a document's keys once; from all finished
    ones where there are too few)."""
    check = run.config["check"]
    rng = np.random.default_rng([run.seed, 11])
    longest = max(finished, key=lambda r: len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    entered = [r for r in rest if shared.get(r["rid"], 0) > 0]
    want = int(check["sample_requests"]) - 1
    if len(entered) >= want:
        rest = entered
    doc = int(run.traffic["shared_prefix"]["length"]) \
        if run.traffic.get("shared_prefix") else 0
    head = np.asarray(longest["tokens"][:doc])
    same = [r for r in rest if doc and len(r["tokens"]) > doc
            and np.array_equal(np.asarray(r["tokens"][:doc]), head)]
    if len(same) >= want:
        rest = same
    k = min(want, len(rest))
    return [longest] + [rest[int(i)] for i in
                        rng.choice(len(rest), size=k, replace=False)]


def verify(run, state, plan, samples) -> list:
    """As ``drivers/serve_minicpmsala.py`` compares: the longest request the
    window finished and five that entered from cached pages (latent pages
    AND index keys), each over its WHOLE length against the reference's full
    pass (index scores and an exact selection for every query; no page, no chunk,
    no list), whose keys of a document's whole segments are computed once
    and kept (``reference.forward``'s memo). Each limit of ``check.limits``
    holds one statistic (``serve_mistral4.STATISTICS``) of the gaps by which
    the served tokens' reference logits lie below the reference's best; all
    three are printed beside it. At least ``check.min_shared_requests`` of
    the sampled requests must have entered from cached pages, and the index
    keys that the cache holds for their documents (read out of the pool
    before the engine goes) lie within ``check.cached_index_key_gap`` of the
    reference's on every page of every layer (:func:`index_key_gaps`): the
    served tokens alone do not show a shared page whose index keys were
    never written."""
    check = run.config["check"]
    limits = check["limits"]
    sharing = bool(run.config["engine"]["prefix_cache"])
    engine = state.pop("engine")
    finished = samples["finished"]
    shared = state["sink"].shared_pages if sharing else {}
    picked = pick(run, finished, shared) if finished else []
    held = {}   # by document: what the cache holds of its index keys
    doc = int((run.traffic.get("shared_prefix") or {}).get("length", 0))
    for rec in picked:
        tag = np.asarray(rec["tokens"][:doc], np.int32).tobytes()
        if sharing and shared.get(rec["rid"], 0) > 0 and tag not in held:
            held[tag] = (rec, cached_index_keys(engine,
                                                rec["tokens"][:rec["n_prompt"]]))
    del engine  # the slabs go before the reference's activations come
    gc.collect()
    if not finished:
        return [{"name": name, "value": float("inf"), "limit": limit,
                 "ok": False, "why": "no request finished"}
                for name, limit in limits.items()]
    buckets = run.config["engine"]["buckets"]
    pad_to = max(b[0] + b[1] for b in buckets)
    max_out = max(b[1] for b in buckets)
    segment = int(check.get("reference_segment", reference.SEGMENT))
    memo = {}
    gaps, ctrl, agree, served = [], [], [], 0
    for rec in picked:
        if len(rec["tokens"]) != rec["n_prompt"] + rec["steps"]:
            gaps.append(np.asarray([np.inf]))
            continue
        got = reference.served_gaps(
            state["params"], run.config, rec["tokens"], rec["n_prompt"],
            pad_to, max_out, control=run.control, segment=segment, memo=memo,
            gathered=True)
        gaps.append(got["gaps"])
        agree.append(got["argmax_agree"])
        served += len(got["gaps"])
        if run.control:
            ctrl.append(got["control_gaps"])
    allg = np.concatenate(gaps)
    facts = {"requests": len(picked), "served_tokens": served,
             "longest": max(len(r["tokens"]) for r in picked),
             "argmax_agree": float(np.mean(agree)) if agree else 0.0,
             "gap_max": float(allg.max()),
             "gap_p99": float(np.percentile(allg, 99)),
             "gap_mean": float(allg.mean())}
    out = []
    for name, limit in limits.items():
        value = float(STATISTICS[name](allg))
        out.append({"name": name, "value": value, "limit": limit,
                    "ok": bool(value < limit), **facts})
    if sharing and "cached_index_key_gap" in check:
        page_len = int(run.config["engine"]["page_len"])
        per_page = [index_key_gaps(keys, reference.kept_index_keys(
            memo, rec["tokens"][:-1], pad_to, segment), page_len)
            for rec, keys in held.values()]
        per_page = np.concatenate(per_page) if per_page else np.zeros(0)
        worst = float(per_page.max()) if per_page.size else float("inf")
        out.append({"name": "cached_index_key_gap", "value": worst,
                    "limit": check["cached_index_key_gap"],
                    "ok": bool(worst < check["cached_index_key_gap"]),
                    "documents": len(held), "pages_x_layers": per_page.size,
                    "mean": float(per_page.mean()) if per_page.size else 0.0})
    if sharing:
        hits = sum(shared.get(rec["rid"], 0) > 0 for rec in picked)
        need = min(int(check["min_shared_requests"]), len(picked))
        out.append({"name": "sampled_requests_shared", "value": hits,
                    "limit": need, "ok": bool(hits >= need),
                    "finished_shared": sum(
                        shared.get(r["rid"], 0) > 0 for r in finished),
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
