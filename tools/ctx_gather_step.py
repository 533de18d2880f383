"""What fetching a row's context out of a page slab costs a prefill chunk,
alone on the chip.

    chiprun --chips 1 -- python3 tools/ctx_gather_step.py [--shapes ...]

A prefill chunk of a spec model (``models/hybrid.py``
``_lm_prefill_paged_spec_jit``) first fetches, for every page-owning layer,
the pages the row's table names out of the layer's slab ``(num_pages,
page_len, width)`` into one context ``(table * page_len, width)``, behind an
optimization barrier. This times that fetch at the slab and the widest
prefill table of each spec cell (``olmohybrid``: 320 pages of 256 x 3840
bfloat16 = 629 MB, a table of 17; ``laguna``, ``falconh1``, ``mistral4``: a
latent entry stored 384 wide, the table padded to the flash kernel's key
blocks; ``lfm2``), four forms a shape:

- ``gather``: ``slab[table]``, one advanced-index gather, the chunk's form
  until PR 45. A row wider than 1024 lanes (3840) XLA cuts into pieces of
  1024 and slices each piece off the WHOLE slab before it gathers;
- ``slices``: one page-sized ``dynamic_slice`` a table entry, joined: PR
  45's first form. Alone it is the bytes; inside the chunk the compiler
  fused the slices with the chunk's page writes and rematerialized around
  them, and the rest of the chunk lost most of what the fetch had gained;
- ``dma``: :func:`~marlin_tpu.ops.paged_attention.fetch_pages`, one kernel
  a slab that copies the table's pages HBM to HBM, opaque to the compiler:
  the chunk's form at every width since PR 45;
- ``copy``: the bytes' own time on this chip, the same number of pages
  copied as ONE contiguous run from a dynamic start (a read and a write of
  the context's bytes, no table).

Every line times eight fetches in ONE program (a chunk of the Olmo-Hybrid
cell makes eight: K and V of four layers), each from a slab and a table
that are parameters of their own, and the same with twenty-four: a program
of eight fetches of tens of microseconds reads the host's dispatch (~0.8 ms
a program here, 100 us a fetch), so ``us_a_slab`` is the SLOPE, what a
fetch more adds, and ``us_a_slab_of_8`` the eight's mean as the host sees
it. One JSON line a form: those two,
``least_us`` (the context's bytes read and written once at 819 GB/s: a
reading under it means the compiler took the fetch out),
``ops_over_slab`` (the operations of the compiled program, all eight
fetches, whose OUTPUT is as large as the slab or a lane piece of it, by
name: a form whose cost goes with ``num_pages`` shows a ``fusion`` or a
``slice`` here, the others nothing), and for ``slices`` and ``dma``
``max_abs_diff_from_gather`` (0.0: the same elements, bit for bit).

How to read it: ``dma`` and ``slices`` beside ``copy`` say whether anything
but the bytes is left; ``gather`` beside them is what a slab gives back a
chunk and layer array (at a row of up to 1024 lanes nothing: the gather is
a copy there too). Ends with ``{"ok": true, "device": ...}``; needs a TPU (a time from
the CPU says nothing). No engine, no model."""

from __future__ import annotations

import argparse
import collections
import json
import pathlib
import re
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

PAGE_LEN, HBM = 256, 819e9
# the cells' slabs and widest prefill tables (benchmarks/configs: num_pages;
# a token's row in a layer's K or V slab, or the latent entry as stored; the
# widest bucket's pages and a chunk's: kvpool.PagedGroup.table_width, the
# latent table padded by hybrid.flash_table_pages)
SHAPES = {
    "olmohybrid": dict(pages=320, width=3840, table=17),
    "laguna": dict(pages=769, width=1024, table=36),
    "falconh1": dict(pages=641, width=512, table=24),
    "mistral4": dict(pages=2561, width=384, table=76),
    "lfm2": dict(pages=1537, width=512, table=26),
}
_CHAIN = (8, 24)  # fetches in the two timed programs


def _time_call(fn, args, calls: int = 4, repeats: int = 5) -> float:
    """Seconds a call: the least mean over ``repeats`` trains of ``calls``
    back-to-back dispatches, each train ended by ``block_until_ready``."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def _chained(fetch):
    """As many fetches ``fetch(slab, table)`` as it is handed slabs, in one
    program, each pinned
    by the barrier the prefill program has, each from a slab and a table
    that are parameters of their own (the chunk's eight slabs are eight
    arrays: of ONE slab the compiler slices the pieces once for all eight
    gathers) and every context an OUTPUT (a context of which one entry is
    read is never assembled). The chip runs a program's operations one after
    another, so the fetches need no dependence on each other."""
    import jax

    @jax.jit
    def many(slabs, tables):
        return [jax.lax.optimization_barrier(fetch(slab, table))
                for slab, table in zip(slabs, tables)]
    return many


def _ops_over_slab(text: str, pages: int) -> dict:
    """The compiled program's operations whose output leads with the slab's
    pages, counted by name; what only hands the slab on is left out."""
    found = collections.Counter()
    for m in re.finditer(
            r"= \w+\[(\d+),%d,\d+\]\S* ([\w\-]+)\(" % PAGE_LEN, text):
        if int(m.group(1)) == pages and m.group(2) not in (
                "parameter", "get-tuple-element", "bitcast"):
            found[m.group(2)] += 1
    return dict(found)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=list(SHAPES),
                    choices=list(SHAPES))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from marlin_tpu.ops.paged_attention import fetch_pages

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": "not a TPU"}))
        return 1
    for name in args.shapes:
        shape = SHAPES[name]
        pages, width, W = shape["pages"], shape["width"], shape["table"]
        slab = jax.random.normal(jax.random.key(0), (pages, PAGE_LEN, width),
                                 jnp.bfloat16)
        # a row's pages lie anywhere in the slab; the table's tail is the
        # dummy page, as a chunk's spill entries are
        rng = np.random.default_rng(0)
        table = np.zeros(W, np.int32)
        table[:W - 2] = rng.choice(np.arange(1, pages), W - 2, replace=False)
        table = jnp.asarray(table)
        forms = {
            "gather": lambda t, tb: t[tb].reshape(-1, *t.shape[2:]),
            "slices": lambda t, tb: jnp.concatenate([
                jax.lax.dynamic_index_in_dim(t, tb[j], 0, keepdims=False)
                for j in range(W)]),
            "dma": lambda t, tb: fetch_pages(t, tb).reshape(
                -1, *t.shape[2:]),
            "copy": lambda t, tb: jax.lax.dynamic_slice_in_dim(
                t, tb[0], W, 0).reshape(-1, *t.shape[2:]),
        }
        least = 2.0 * W * PAGE_LEN * width * 2 / HBM
        for form, fetch in forms.items():
            many = _chained(fetch)
            # one buffer handed in n times: n parameters to the compiler
            few, more = (([slab] * n, [table] * n) for n in _CHAIN)
            text = many.lower(*few).compile().as_text()
            t_few, t_more = (_time_call(many, ops) for ops in (few, more))
            seconds = (t_more - t_few) / (_CHAIN[1] - _CHAIN[0])
            line = {"shape": name, "form": form, "slab": [pages, PAGE_LEN,
                                                          width],
                    "table": W, "slab_mb": pages * PAGE_LEN * width * 2 / 1e6,
                    "context_mb": W * PAGE_LEN * width * 2 / 1e6,
                    "us_a_slab": 1e6 * seconds,
                    "us_a_slab_of_8": 1e6 * t_few / _CHAIN[0],
                    "least_us": 1e6 * least,
                    "ops_over_slab": _ops_over_slab(text, pages)}
            if form in ("slices", "dma"):
                line["max_abs_diff_from_gather"] = float(jnp.abs(
                    jax.jit(fetch)(slab, table).astype(jnp.float32)
                    - jax.jit(forms["gather"])(slab, table).astype(
                        jnp.float32)).max())
            print(json.dumps(line), flush=True)
        del slab
    print(json.dumps({"ok": True, "device": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
