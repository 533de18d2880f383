#!/usr/bin/env python3
"""The ``serve_falconh1`` programs against the plain reference in float32 at
highest matmul precision, at the published WIDTHS (two layers and an eighth
of the vocabulary, so that float32 weights fit): chunked paged prefill of a
prompt that crosses chunk, page and scan-block boundaries and ends inside a
scan block, then decode through the pages (of 128 positions) and the state
slot with the Pallas kernels and with the gather formulation. Prints the largest difference in
logits. A builder's check on the chip (through ``chiprun``); on a CPU it runs
the same at a small prompt.

    python3 benchmarks/f32_check_falconh1.py [prompt_len] [steps]
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

    from benchmarks.drivers import serve_falconh1 as driver
    from benchmarks.reference import serve_falconh1 as reference
    from marlin_tpu.models.transformer import (init_kv_pages,
                                               lm_decode_paged,
                                               lm_prefill_paged)

    on_tpu = jax.devices()[0].platform == "tpu"
    n = int(argv[0]) if argv else (2341 if on_tpu else 300)
    steps = int(argv[1]) if len(argv) > 1 else 6
    with open(os.path.join(HERE, "configs", "falcon-h1-34b-l6.json")) as f:
        cfg = json.load(f)
    cfg.update(num_hidden_layers=2, vocab_size=32640,
               param_dtype="float32", compute_dtype="float32")
    # pages of 128, half the cell's: the attention kernel's float32 blocks
    # at 256 ask for 98.8 MB of the 96 MB of scoped VMEM (my chip run, PR 37)
    page, chunk = 128, cfg["engine"]["prefill_chunk"]
    spec = driver.model_spec(cfg)
    out = {"device": jax.devices()[0].device_kind, "prompt": n,
           "steps": steps}
    with jax.default_matmul_precision("highest"):
        params = driver.make_weights(cfg, 7)
        prompt = np.random.default_rng(7).integers(
            0, cfg["vocab_size"], n).astype(np.int32)
        need = -(-(n + steps) // page)
        table = np.zeros(need + chunk // page, np.int32)
        table[:need] = np.arange(1, need + 1)
        padded = np.zeros(-(-n // chunk) * chunk, np.int32)
        padded[:n] = prompt
        no_ring = np.zeros(0, np.int32)
        for kernel in ("pallas", "gather"):
            pages = init_kv_pages(params, need + 2, page, spec,
                                  state_slots=3)
            for cs in range(0, len(padded), chunk):
                pages, first, _, logits = lm_prefill_paged(
                    params, pages, (table, no_ring, 2),
                    padded[cs:cs + chunk], cs, n, heads=spec, page_len=page)
            toks, served = list(prompt) + [int(first)], [np.asarray(logits)]
            gt = np.stack([np.zeros(need, np.int32), table[:need]])
            z = np.zeros(2)
            for t in range(steps - 1):
                pages, nxt, _, logits = lm_decode_paged(
                    params, pages,
                    (gt, np.zeros((2, 0), np.int32), np.array([0, 2])),
                    np.array([0, n + t]), np.array([0, toks[-1]]), z, z, z,
                    np.ones(2), z, heads=spec, page_len=page, kernel=kernel)
                toks.append(int(nxt[1]))
                served.append(np.asarray(logits[1]))
            del pages
            want = np.asarray(reference.logits_at(
                params, cfg, toks[:-1], np.arange(n - 1, len(toks) - 1),
                -(-len(toks) // 256) * 256))
            diff = np.abs(np.stack(served) - want).max(axis=-1)
            out[kernel] = {"logit_scale": float(np.abs(want).max()),
                           "logit_std": float(want.std()),
                           "prefill_diff": float(diff[0]),
                           "decode_diff": float(diff[1:].max()),
                           "argmax_agree": float((
                               want.argmax(-1) == np.asarray(toks[n:])
                           ).mean())}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
