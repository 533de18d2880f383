#!/usr/bin/env python3
"""Compile the cells' programs at their real sizes for a described (not
attached) ``v5e:2x2`` and print the compiler's memory accounting.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_check.py [config ...]

Run before the first chip call and after a change of a size: what the TPU
compiler refuses here costs no chip time. Nothing runs, so this says nothing
about results or times; its peaks go into the configuration files' sizing
arithmetic. It counts one program at a time, not what else the process keeps
on the device (``resident`` below adds the arguments that stay).
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


def report(name, compiled, **extra):
    m = compiled.memory_analysis()
    print(json.dumps({
        "program": name,
        "argument_gb": m.argument_size_in_bytes / GB,
        "output_gb": m.output_size_in_bytes / GB,
        "temp_gb": m.temp_size_in_bytes / GB,
        "alias_gb": m.alias_size_in_bytes / GB,
        "peak_gb": m.peak_memory_in_bytes / GB, **extra}), flush=True)
    return compiled


def check_matmul(cfg, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from marlin_tpu.parallel.matmul import matmul_padded

    n, shape = int(cfg["n"]), tuple(cfg["mesh"])
    chips = shape[0] * shape[1]
    mesh = Mesh(np.asarray(topo.devices)[:chips].reshape(shape),
                ("rows", "cols"))
    rows = NamedSharding(mesh, P("rows", None))
    out = NamedSharding(mesh, P("rows", "cols") if shape[1] > 1
                        else P("rows", None))
    a = jax.ShapeDtypeStruct((n, n), jnp.dtype(cfg["dtype"]), sharding=rows)
    c = report(f"{cfg['name']}: multiply n={n} mesh={shape}", jax.jit(
        lambda x, y: matmul_padded(x, y, (n, n, n), out, (n, n),
                                   strategy=cfg["strategy"],
                                   precision=cfg["precision"])
    ).trace(a, a).lower().compile(), per_chip=True)
    text = c.as_text()
    print(json.dumps({"collectives": sorted({w for w in (
        "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
        "all-to-all") if w in text})}), flush=True)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                               sharding=NamedSharding(mesh, P()))
    report(f"{cfg['name']}: one operand from the seed", jax.jit(
        lambda k: jax.random.uniform(k, (n, n), jnp.dtype(cfg["dtype"]),
                                     -1.0, 1.0),
        out_shardings=rows).trace(key).lower().compile())


def check_serve(cfg, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import marlin_tpu as mt
    from benchmarks.drivers import serve as driver
    from marlin_tpu.models.transformer import (_lm_decode_paged_jit,
                                               _lm_prefill_paged_jit,
                                               init_kv_pages)
    from marlin_tpu.serving.kvpool import PagedGroup

    one = NamedSharding(Mesh(np.array([topo.devices[0]]).reshape(1, 1),
                             ("a", "b")), P())
    eng = cfg["engine"]
    heads, B, page_len = int(cfg["n_heads"]), eng["max_batch"], eng["page_len"]

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), x.dtype, sharding=one), tree)

    def st(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    params = sds(jax.eval_shape(lambda: driver.make_weights(cfg, 0)))
    pages = sds(jax.eval_shape(lambda pp: init_kv_pages(
        pp, eng["num_pages"], page_len, heads, cfg["compute_dtype"]), params))
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    slab = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pages))
    print(json.dumps({"resident": {"weights_gb": weights / GB,
                                   "page_slab_gb": slab / GB}}), flush=True)
    for bucket in eng["buckets"]:
        g = PagedGroup(tuple(bucket), B, page_len, eng["prefill_chunk"])
        report(f"{cfg['name']}: lm_prefill_paged bucket={bucket}",
               _lm_prefill_paged_jit.trace(
                   params, pages, st((g.table_width,)), st((g.chunk,)),
                   st(()), st(()), st((), jnp.uint32), st((), jnp.float32),
                   st((), jnp.float32), st(()), heads=heads,
                   page_len=page_len, compute_dtype=cfg["compute_dtype"],
                   moe=None).lower().compile())
        with mt.config_context(pallas_interpret=False):
            c = report(f"{cfg['name']}: lm_decode_paged(pallas) "
                       f"bucket={bucket}", _lm_decode_paged_jit.trace(
                           params, pages, st((B, g.pages_per_row)), st((B,)),
                           st((B,)), st((B,)), st((B,), jnp.uint32),
                           st((B,), jnp.float32), st((B,), jnp.float32),
                           st((B,)), heads=heads, page_len=page_len,
                           compute_dtype=cfg["compute_dtype"], moe=None,
                           kernel="pallas").lower().compile())
        assert "tpu_custom_call" in c.as_text(), "no Pallas kernel inside"


def main(argv):
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = argv or sorted(f[:-5] for f in os.listdir(
        os.path.join(HERE, "configs")) if f.endswith(".json"))
    for name in names:
        with open(os.path.join(HERE, "configs", name + ".json")) as f:
            cfg = json.load(f)
        {"matmul": check_matmul, "serve": check_serve}[cfg["driver"]](cfg, topo)


if __name__ == "__main__":
    main(sys.argv[1:])
