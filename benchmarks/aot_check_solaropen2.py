#!/usr/bin/env python3
"""Compile the ``serve_solaropen2`` configurations' programs at their real
sizes for a described (not attached) ``v5e:2x2`` and print the weights, the
page slab, the state slab (rows' slots and snapshot slots) and each
program's peak (the sibling of ``aot_check_olmohybrid.py``; this family
holds a share of the experts in every layer).

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check_solaropen2.py [config ...]

Nothing runs: this says nothing about results or times. Its numbers go into
the configuration file's ``assumed``.
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

GB = 1e9


def check(cfg, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import marlin_tpu as mt
    from benchmarks.aot_check import report
    from benchmarks.drivers import serve_solaropen2 as driver
    from marlin_tpu.models import hybrid
    from marlin_tpu.serving.kvpool import PagedGroup, decode_pages

    one = SingleDeviceSharding(topo.devices[0])
    eng = cfg["engine"]
    B, page_len = eng["max_batch"], eng["page_len"]
    spec = driver.model_spec(cfg)
    buckets = [tuple(b) for b in eng["buckets"]]
    slots = eng["state_slots"] + eng["snapshot_slots"]

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=one), tree)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))

    params = sds(jax.eval_shape(
        lambda: hybrid.init_params(spec, jax.random.key(0))))
    pages = sds(jax.eval_shape(lambda: hybrid.init_kv_pages(
        spec, eng["num_pages"], 0, page_len, state_slots=slots)))
    kv = sum(nbytes(pages[f"l{i}"]) for i, ly in enumerate(spec.layers)
             if ly.attn == "full")
    state = sum(nbytes(pages[f"l{i}"]) for i, ly in enumerate(spec.layers)
                if ly.attn == "kda")
    print(json.dumps({"resident": {
        "weights_gb": nbytes(params) / GB, "kv_slab_gb": kv / GB,
        "state_slab_gb": state / GB,
        "state_slot_bytes": spec.state_slot_bytes(),
        "page_bytes": spec.page_values("full", page_len) * 2,
        "total_gb": (nbytes(params) + kv + state) / GB}}), flush=True)
    for ly in dict.fromkeys(spec.layers):
        report(f"{cfg['name']}: one {ly.attn} layer's draw",
               hybrid.init_layer_params.trace(
                   spec, ly, st((), jax.random.key(0).dtype)).lower()
               .compile())
    with mt.config_context(pallas_interpret=False):
        for bucket in buckets:
            g = PagedGroup(bucket, B, page_len, eng["prefill_chunk"], ring=0,
                           stateful=True)
            report(f"{cfg['name']}: lm_prefill_paged bucket={list(bucket)} "
                   f"chunk={g.chunk}",
                   hybrid._lm_prefill_paged_spec_jit.trace(
                       params, pages, st((g.table_width,)), st((0,)),
                       st((g.chunk,)), st(()), st(()), st((), jnp.uint32),
                       st((), jnp.float32), st((), jnp.float32), st(()),
                       spec=spec, page_len=page_len,
                       state_slot=st(())).lower().compile())
        # the engine's one decode program has the widest bucket's table
        W = decode_pages(buckets, page_len)
        c = report(
            f"{cfg['name']}: lm_decode_paged(pallas) table={W}",
            hybrid._lm_decode_paged_spec_jit.trace(
                params, pages, st((B, W)), st((B, 0)), st((B,)), st((B,)),
                st((B,)), st((B,), jnp.uint32), st((B,), jnp.float32),
                st((B,), jnp.float32), st((B,)), spec=spec,
                page_len=page_len, kernel="pallas", prev_tokens=st((B,)),
                prev_index=st((B,)), state_slots=st((B,))).lower().compile())
        report(f"{cfg['name']}: state_slot_copy (a snapshot)",
               hybrid._state_slot_copy_jit.trace(
                   pages, st(()), st(()), spec=spec).lower().compile())
    text = c.as_text()
    # the attention kernel, a state update a KDA layer, three grouped
    # matmuls an expert layer
    kernels = 1 + 3 + 3 * len(spec.layers)
    assert text.count("tpu_custom_call") >= kernels, \
        "the attention, state-update and grouped-matmul kernels are not " \
        "all inside"


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv or ["solar-open2-ep8-l4"]:
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            check(json.load(f), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
