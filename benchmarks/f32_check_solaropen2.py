#!/usr/bin/env python3
"""The ``serve_solaropen2`` programs against the plain reference in float32
at highest matmul precision, at the published WIDTHS (one period of the
layer pattern: a gated NoPE GQA layer and three KDA layers; 16 of the 320
experts held from the ninth on, the router 320 wide with its 8 picks, and a
sixteenth of the vocabulary, so that float32 weights fit): chunked paged
prefill of a
prompt A that crosses chunk, page and block boundaries and ends inside a
block, a snapshot of its state behind the chunk that ends at position 1024,
then a second prompt B that shares A's first 1024 tokens (8 pages of 128),
takes A's pages, ENTERS FROM THE SNAPSHOT and prefills only its own tail,
then decodes through the pages and the state slot with the Pallas kernels
and with the gather formulation. Prints the largest difference in logits.
A builder's check on the chip (through ``chiprun``); on a CPU it runs the
same at small prompts.

    python3 benchmarks/f32_check_solaropen2.py [steps]
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv):
    import jax
    import numpy as np

    from benchmarks.drivers import serve_solaropen2 as driver
    from benchmarks.reference import serve_solaropen2 as reference
    from marlin_tpu.models import hybrid
    from marlin_tpu.models.transformer import (init_kv_pages,
                                               lm_decode_paged,
                                               lm_prefill_paged)

    on_tpu = jax.devices()[0].platform == "tpu"
    steps = int(argv[0]) if argv else 6
    with open(os.path.join(HERE, "configs", "solar-open2-ep8-l4.json")) as f:
        cfg = json.load(f)
    cfg.update(n_routed_experts=16, vocab_size=12288,
               param_dtype="float32", compute_dtype="float32")
    cfg["deployment_share"] = dict(cfg["deployment_share"], first_expert=8)
    if not on_tpu:   # the same control flow at a size a CPU finishes
        cfg.update(hidden_size=256, moe_intermediate_size=128, vocab_size=512,
                   num_attention_heads=4, num_key_value_heads=2,
                   linear_attn_config=dict(cfg["linear_attn_config"],
                                           num_heads=2))
    page, chunk = (256, 512) if on_tpu else (16, 64)
    shared = 2 * chunk                     # 1024: 8 pages, 2 chunks
    n_a, n_b = shared + (76 if on_tpu else 13), shared + (301 if on_tpu else 37)
    spec = driver.model_spec(cfg)
    out = {"device": jax.devices()[0].device_kind, "prompt_a": n_a,
           "prompt_b": n_b, "shared": shared, "steps": steps,
           "page": page, "chunk": chunk}
    rng = np.random.default_rng(7)
    a = rng.integers(0, cfg["vocab_size"], n_a).astype(np.int32)
    b = np.concatenate([a[:shared], rng.integers(
        0, cfg["vocab_size"], n_b - shared).astype(np.int32)])
    no_ring = np.zeros(0, np.int32)

    def table_of(first, need):
        t = np.zeros(need + chunk // page, np.int32)
        t[:need] = np.arange(first, first + need)
        return t

    def prefill(params, pages, prompt, table, slot, start, snapshot=None):
        n = len(prompt)
        padded = np.zeros(-(-n // chunk) * chunk, np.int32)
        padded[:n] = prompt
        for cs in range(start, len(padded), chunk):
            pages, first, _, logits = lm_prefill_paged(
                params, pages, (table, no_ring, slot),
                padded[cs:cs + chunk], cs, n, heads=spec, page_len=page)
            if snapshot and cs + chunk == snapshot[0]:
                pages = hybrid.state_slot_copy(pages, slot, snapshot[1],
                                               spec)
        return pages, int(first), np.asarray(logits)

    with jax.default_matmul_precision("highest"):
        params = driver.make_weights(cfg, 7)
        need_a = -(-n_a // page)
        need_b = -(-(n_b + steps) // page)
        pad = -(-(n_b + steps) // 256) * 256
        want_a = np.asarray(reference.logits_at(
            params, cfg, a, np.asarray([n_a - 1]), pad))[0]
        for kernel in ("pallas", "gather"):
            pages = init_kv_pages(params, need_a + need_b + 2, page, spec,
                                  state_slots=5)
            # A: slot 1, pages 1..; its state after 1024 tokens kept in slot 4
            pages, _, logits_a = prefill(params, pages, a,
                                         table_of(1, need_a), 1, 0,
                                         snapshot=(shared, 4))
            # B: A's first 8 pages, its own after them; slot 2 entered from
            # the snapshot; prefill from the boundary
            tb = table_of(need_a + 1, need_b)
            tb[:shared // page] = np.arange(1, shared // page + 1)
            pages = hybrid.state_slot_copy(pages, 4, 2, spec)
            pages, first, logits = prefill(params, pages, b, tb, 2, shared)
            toks, served = list(b) + [first], [logits]
            gt = np.stack([np.zeros(need_b, np.int32), tb[:need_b]])
            z = np.zeros(2)
            for t in range(steps - 1):
                pages, nxt, _, logits = lm_decode_paged(
                    params, pages,
                    (gt, np.zeros((2, 0), np.int32), np.array([0, 2])),
                    np.array([0, n_b + t]), np.array([0, toks[-1]]), z, z, z,
                    np.ones(2), z, heads=spec, page_len=page, kernel=kernel)
                toks.append(int(nxt[1]))
                served.append(np.asarray(logits[1]))
            del pages
            want = np.asarray(reference.logits_at(
                params, cfg, toks[:-1], np.arange(n_b - 1, len(toks) - 1),
                pad))
            diff = np.abs(np.stack(served) - want).max(axis=-1)
            out[kernel] = {
                "logit_scale": float(np.abs(want).max()),
                "logit_std": float(want.std()),
                "prefill_diff_a": float(np.abs(logits_a - want_a).max()),
                "prefill_diff_b_from_snapshot": float(diff[0]),
                "decode_diff": float(diff[1:].max()),
                "argmax_agree": float((
                    want.argmax(-1) == np.asarray(toks[n_b:])).mean())}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
