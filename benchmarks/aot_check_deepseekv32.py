#!/usr/bin/env python3
"""Compile the ``serve_deepseekv32`` configurations' programs at their real
sizes for a described (not attached) ``v5e:2x2`` and print the weights, the
latent page slab with its index keys and each program's peak (the sibling of
``aot_check_mistral4.py``; ``num_pages`` and ``prefill_chunk`` may be given
after a configuration's name as ``name:num_pages:chunk``).

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check_deepseekv32.py [config ...]

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
    from benchmarks.drivers import serve_deepseekv32 as driver
    from marlin_tpu.models import hybrid
    from marlin_tpu.serving.kvpool import PagedGroup, decode_pages

    one = SingleDeviceSharding(topo.devices[0])
    eng = cfg["engine"]
    B, page_len = eng["max_batch"], eng["page_len"]
    spec = driver.model_spec(cfg)
    buckets = [tuple(b) for b in eng["buckets"]]

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
        spec, eng["num_pages"], 0, page_len)))
    print(json.dumps({"resident": {
        "weights_gb": nbytes(params) / GB,
        "latent_slab_and_index_keys_gb": nbytes(pages) / GB,
        "entry_width": spec.latent.entry_width,
        "total_gb": (nbytes(params) + nbytes(pages)) / GB}}), flush=True)
    report(f"{cfg['name']}: one layer's draw",
           hybrid.init_layer_params.trace(
               spec, spec.layers[0],
               st((), jax.random.key(0).dtype)).lower().compile())
    report(f"{cfg['name']}: kv_page_copy",
           hybrid._kv_page_copy_spec_jit.trace(
               pages, st(()), st(()), spec=spec).lower().compile())
    with mt.config_context(pallas_interpret=False):
        for bucket in buckets:
            g = PagedGroup(bucket, B, page_len, eng["prefill_chunk"], ring=0)
            report(f"{cfg['name']}: lm_prefill_paged bucket={list(bucket)}",
                   hybrid._lm_prefill_paged_spec_jit.trace(
                       params, pages, st((g.table_width,)), st((0,)),
                       st((g.chunk,)), st(()), st(()), st((), jnp.uint32),
                       st((), jnp.float32), st((), jnp.float32), st(()),
                       spec=spec, page_len=page_len).lower().compile())
        # the engine's one decode program has the widest bucket's table
        W = decode_pages(buckets, page_len)
        c = report(
            f"{cfg['name']}: lm_decode_paged(pallas) table={W}",
            hybrid._lm_decode_paged_spec_jit.trace(
                params, pages, st((B, W)), st((B, 0)), st((B,)), st((B,)),
                st((B,)), st((B,), jnp.uint32), st((B,), jnp.float32),
                st((B,), jnp.float32), st((B,)), spec=spec,
                page_len=page_len, kernel="pallas").lower().compile())
    text = c.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel inside"
    assert "gmm" in text, "no grouped matmul inside"


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for arg in argv or ["deepseek-v32-ep16-l5"]:
        name, *sizes = arg.split(":")
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            cfg = json.load(f)
        for key, value in zip(("num_pages", "prefill_chunk"), sizes):
            cfg["engine"][key] = int(value)
        check(cfg, topo)


if __name__ == "__main__":
    main(sys.argv[1:])
