#!/usr/bin/env python3
"""Compile the ``serve_laguna`` configurations' programs at their real sizes
for a described (not attached) ``v5e:2x2`` and print the weights, both page
slabs and each program's peak (the sibling of ``aot_check.py``, which knows
the dense drivers only).

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check_laguna.py [config ...]

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
    from benchmarks.drivers import serve_laguna as driver
    from marlin_tpu.models import hybrid
    from marlin_tpu.serving.kvpool import PagedGroup, group_chunk

    one = SingleDeviceSharding(topo.devices[0])
    eng = cfg["engine"]
    B, page_len = eng["max_batch"], eng["page_len"]
    spec = driver.model_spec(cfg)
    buckets = [tuple(b) for b in eng["buckets"]]
    ring = hybrid.window_ring_pages(
        spec.window, max(group_chunk(b, page_len, eng["prefill_chunk"])
                         for b in buckets), page_len)

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
        spec, eng["num_pages"], eng["window_pages"], page_len)))
    slabs = {kind: nbytes([pages[n] for n in spec.layer_names(kind)])
             for kind in ("full", "sliding")}
    print(json.dumps({"resident": {
        "weights_gb": nbytes(params) / GB,
        "global_slab_gb": slabs["full"] / GB,
        "window_slab_gb": slabs["sliding"] / GB,
        "ring_pages_a_row": ring,
        "total_gb": (nbytes(params) + sum(slabs.values())) / GB}}),
        flush=True)
    report(f"{cfg['name']}: one expert layer's draw",
           hybrid.init_layer_params.trace(
               spec, spec.layers[1], st((), jax.random.key(0).dtype)).lower().compile())
    for bucket in buckets:
        g = PagedGroup(bucket, B, page_len, eng["prefill_chunk"], ring=ring)
        with mt.config_context(pallas_interpret=False):
            report(f"{cfg['name']}: lm_prefill_paged bucket={list(bucket)}",
                   hybrid._lm_prefill_paged_spec_jit.trace(
                       params, pages, st((g.table_width,)), st((ring,)),
                       st((g.chunk,)), st(()), st(()), st((), jnp.uint32),
                       st((), jnp.float32), st((), jnp.float32), st(()),
                       spec=spec, page_len=page_len).lower().compile())
            c = report(
                f"{cfg['name']}: lm_decode_paged(pallas) "
                f"bucket={list(bucket)}",
                hybrid._lm_decode_paged_spec_jit.trace(
                    params, pages, st((B, g.pages_per_row)), st((B, ring)),
                    st((B,)), st((B,)), st((B,)), st((B,), jnp.uint32),
                    st((B,), jnp.float32), st((B,), jnp.float32), st((B,)),
                    spec=spec, page_len=page_len,
                    kernel="pallas").lower().compile())
        text = c.as_text()
        assert "tpu_custom_call" in text, "no Pallas kernel inside"
        assert "gmm" in text, "no grouped matmul inside"


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in argv or ["laguna-s21-ep4-l9"]:
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            check(json.load(f), topo)


if __name__ == "__main__":
    main(sys.argv[1:])
