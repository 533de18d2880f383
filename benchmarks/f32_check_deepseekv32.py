#!/usr/bin/env python3
"""The ``serve_deepseekv32`` programs against the plain reference in float32
at highest matmul precision, at the published WIDTHS (layers 0-1 of the cut:
the dense layer and one expert layer with its 16 held experts, so that
float32 weights fit): chunked paged prefill of a prompt A that crosses
``index_topk`` (2048), a chunk and page edges, then a second prompt B that
shares A's first 2816 tokens (11 pages of 256), takes A's latent pages WITH
THEIR INDEX KEYS and prefills only its own tail from there (a chunk that does
not start on a chunk boundary, every query past ``index_topk``), then decodes
through the pages with the index-score kernel and with the gather
formulation. Prints the largest difference in logits and whether every
selection the programs made for B (every layer, every served position) is
the reference's SET. A builder's check on the chip (through ``chiprun``); on
a CPU it runs the same control flow at a toy size.

    python3 benchmarks/f32_check_deepseekv32.py [steps]
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

TOY = dict(hidden_size=48, num_attention_heads=4, num_key_value_heads=4,
           q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16, index_n_heads=4,
           index_head_dim=16, index_topk=32, intermediate_size=80,
           moe_intermediate_size=24, n_routed_experts=4, vocab_size=97)


def main(argv):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers import serve_deepseekv32 as driver
    from benchmarks.reference import serve_deepseekv32 as reference
    from marlin_tpu.models import hybrid
    from marlin_tpu.models.transformer import (init_kv_pages,
                                               lm_decode_paged,
                                               lm_prefill_paged)
    from marlin_tpu.ops import dsa

    on_tpu = jax.devices()[0].platform == "tpu"
    steps = int(argv[0]) if argv else 6
    with open(os.path.join(HERE, "configs", "deepseek-v32-ep16-l5.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=2, param_dtype="float32",
               compute_dtype="float32")
    if not on_tpu:
        cfg.update(TOY)
        cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                                   original_max_position_embeddings=64)
    # chunks of 512: the flash panel of a chunk below index_topk holds
    # float32 blocks of 192 columns in VMEM (1024 rows of them do not fit)
    page, chunk = (256, 512) if on_tpu else (8, 16)
    shared = 11 * page                 # 2816: past index_topk, mid-chunk
    n_a, n_b = shared + (76 if on_tpu else 13), shared + (301 if on_tpu else 9)
    seg = 512 if on_tpu else 32
    spec = driver.model_spec(cfg)
    out = {"device": jax.devices()[0].device_kind, "prompt_a": n_a,
           "prompt_b": n_b, "shared": shared, "steps": steps,
           "page": page, "chunk": chunk}
    rng = np.random.default_rng(7)
    a = rng.integers(0, cfg["vocab_size"], n_a).astype(np.int32)
    b = np.concatenate([a[:shared], rng.integers(
        0, cfg["vocab_size"], n_b - shared).astype(np.int32)])
    no_ring = np.zeros(0, np.int32)

    # every selection the programs make, as the tests record it
    made = []
    inner = hybrid._attend_selected_tokens

    def recording(q, ctx, scores, n_valid, la, tile, entry_of=None,
                  live=None):
        L = scores.shape[1]
        padded = jnp.pad(scores, ((0, 0), (0, -L % 128)),
                         constant_values=-jnp.inf)
        idx, count = dsa.select_tokens(padded, n_valid,
                                       min(la.indexer.topk, padded.shape[1]))
        jax.debug.callback(lambda *x: made.append([np.asarray(v) for v in x]),
                           n_valid - 1, idx, count, ordered=True)
        return inner(q, ctx, scores, n_valid, la, tile, entry_of, live)

    hybrid._attend_selected_tokens = recording

    def table_of(first, need):
        t = np.zeros(need + chunk // page, np.int32)
        t[:need] = np.arange(first, first + need)
        return t

    def prefill(params, pages, prompt, table, start):
        n = len(prompt)
        padded = np.zeros(start + -(-(n - start) // chunk) * chunk, np.int32)
        padded[:n] = prompt
        for cs in range(start, len(padded), chunk):
            pages, first, _, logits = lm_prefill_paged(
                params, pages, (table, no_ring), padded[cs:cs + chunk], cs, n,
                heads=spec, page_len=page)
        return pages, int(first), np.asarray(logits)

    with jax.default_matmul_precision("highest"):
        params = driver.make_weights(cfg, 7)
        need_a = -(-n_a // page) + chunk // page
        need_b = -(-(n_b + steps) // page) + chunk // page
        pad = -(-(n_b + steps) // seg) * seg
        want_a = reference.logits_at(params, cfg, a, np.asarray([n_a - 1]),
                                     pad, segment=seg)[0]
        for kernel in ("pallas", "gather"):
            pages = init_kv_pages(params, need_a + need_b + 2, page, spec)
            pages, _, logits_a = prefill(params, pages, a,
                                         table_of(1, need_a), 0)
            tb = table_of(need_a + 1, need_b)
            tb[:shared // page] = np.arange(1, shared // page + 1)
            jax.effects_barrier()
            made.clear()
            pages, first, logits = prefill(params, pages, b, tb, shared)
            toks, served = list(b) + [first], [logits]
            gt = np.stack([np.zeros(need_b, np.int32), tb[:need_b]])
            z = np.zeros(2)
            for t in range(steps - 1):
                pages, nxt, _, logits = lm_decode_paged(
                    params, pages, (gt, np.zeros((2, 0), np.int32)),
                    np.array([0, n_b + t]), np.array([0, toks[-1]]), z, z, z,
                    np.ones(2), z, heads=spec, page_len=page, kernel=kernel)
                toks.append(int(nxt[1]))
                served.append(np.asarray(logits[1]))
            del pages
            jax.effects_barrier()
            want = reference.logits_at(
                params, cfg, toks[:-1], np.arange(n_b - 1, len(toks) - 1),
                pad, segment=seg)
            sets = reference.selections(params, cfg, toks[:-1], pad,
                                        segment=seg)
            same = other = 0
            for i, (positions, idx, count) in enumerate(made):
                layer = i % spec.n_layers
                rows = range(len(positions)) if len(positions) > 2 else (1,)
                for r in rows:
                    p, c = int(positions[r]), int(count[r])
                    if len(positions) > 2 and p >= n_b:
                        continue   # a chunk's padding
                    ok = idx[r][:c].tolist() == sets[layer][p]
                    same, other = same + ok, other + (not ok)
            diff = np.abs(np.stack(served) - want).max(axis=-1)
            out[kernel] = {
                "logit_scale": float(np.abs(want).max()),
                "logit_std": float(want.std()),
                "prefill_diff_a": float(np.abs(logits_a - want_a).max()),
                "prefill_diff_b_from_shared_pages": float(diff[0]),
                "decode_diff": float(diff[1:].max()),
                "selections_equal": same, "selections_differ": other,
                "argmax_agree": float((
                    want.argmax(-1) == np.asarray(toks[n_b:])).mean())}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
