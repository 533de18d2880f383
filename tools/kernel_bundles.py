"""The compiler's own schedule of a Pallas kernel, without a chip.

    JAX_PLATFORMS=cpu python3 tools/kernel_bundles.py [--pairs 1 2 4] [--f32]
    JAX_PLATFORMS=cpu python3 tools/kernel_bundles.py --select [L ...]

Compiles ``ops/delta_rule.py:delta_chunk_scan`` at the
``serve.solaropen2-reason128`` cell's prefill chunk (512 positions, 64 heads
of 128 x 128) for a described ``v5e:2x2`` with the TPU compiler's dumps on
(``LIBTPU_INIT_ARGS=--xla_jf_dump_to=... --xla_jf_dump_llo_text=true``, in a
child process: the flags are read when libtpu loads) and reads the kernel's
``final_hlo-static-per-bundle-utilization`` file: one line a bundle (a VLIW
instruction word, one a cycle when nothing stalls) with the slots it fills of
each unit (capacity a bundle: MXU 4, XLU 3, VALU 4, EUP 1, loads 3, stores
1). Prints, for each count of head pairs a grid step holds, one JSON line:
the bundles of ONE grid step (every loop of the kernel is unrolled, so the
file is one pass over the body), bundles a pair, each unit's filled slots,
and the same by windows of 500 bundles: where a unit's column stands at its
capacity the stage waits for that unit, where every column is low it waits
for a chain of results. PR 55 cut the kernel from 4227 to ~3050 bundles a
pair reading this (PERF.md section 6); a time comes only from the chip
(``tools/delta_rule_step.py --kda``). ~40 s a count.

``--select`` reads ``ops/dsa.py:_dsa_select_call`` (PR 57: a tile's token
selection, 32 rows x ``L`` index scores, default the ``serve.deepseekv32-
longctx32`` cell's 67584 and 68608, ``index_topk`` 2048) instead: the same
counts for ONE grid step of 8 rows, and ``loops``: every backward branch as
``[first bundle, last bundle]``. That kernel keeps three loops (the search's
32 passes, the 7 moves below a lane tile, the moves by whole tiles that no
piece is spared), which the compiler may rotate, so the file holds part of
a body once: weigh a loop's bundles by its turns. A loop's turn costs ~60
cycles more than its bundles on the chip, and the search waits ~160 a pass
for a lane reduction: the first kernel, all loops, read 1.8 x its count, the
moves written out read their count (PERF.md section 6, PR 57)."""

from __future__ import annotations

import argparse
import glob
import json
import os
import pathlib
import shutil
import subprocess
import sys
import tempfile

ROOT = str(pathlib.Path(__file__).resolve().parent.parent)
UNITS = ("MXU", "XLU", "VALU", "EUP", "VLOAD", "VLOAD:FILL", "VSTORE",
         "VSTORE:SPILL", "SALU")


def _compile(pairs: int, f32: bool) -> None:
    """In the child: compile the scan for one described chip."""
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from marlin_tpu.ops import delta_rule
    from marlin_tpu.utils.aot import tpu_topology

    delta_rule._KDA_PAIRS = tuple(sorted({1, pairs}))
    one = NamedSharding(Mesh(np.array(
        [tpu_topology("v5e:2x2").devices[0]]).reshape(1, 1), ("a", "b")), P())
    T, H, K = 512, 64, 128
    cd = jnp.float32 if f32 else jnp.bfloat16

    def st(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    jax.jit(lambda q, k, v, g, b, s, n: delta_rule.delta_chunk_scan(
        q, k, v, g, b, s, block=64, valid=n, interpret=False)).lower(
            st((T, H, K), cd), st((T, H, K), cd), st((T, H, K), cd),
            st((T, H, K)), st((T, H)), st((K, H, K)),
            st((), jnp.int32)).compile()


def _compile_select(L: int) -> None:
    """In the child: compile the selection of one tile for one described
    chip."""
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from marlin_tpu.ops import dsa
    from marlin_tpu.utils.aot import tpu_topology

    one = NamedSharding(Mesh(np.array(
        [tpu_topology("v5e:2x2").devices[0]]).reshape(1, 1), ("a", "b")), P())
    dsa._dsa_select_call.trace(
        jax.ShapeDtypeStruct((32, L), jnp.float32, sharding=one),
        jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one),
        k=2048, interpret=False).lower().compile()


def loops(path: str) -> list:
    """Every backward branch of a ``final_bundles`` file: ``[first bundle,
    last bundle]`` of what it repeats."""
    import re

    found = []
    for line in open(path):
        at = re.match(r"\s*(0x[0-9a-f]+|\d+)\s", line)
        to = re.search(r"sbr\.rel .*?target bundleno = (\d+)", line)
        if at and to and int(to.group(1)) <= int(at.group(1), 0):
            found.append([int(to.group(1)), int(at.group(1), 0)])
    return found


def _dumped(child_args: list, pattern: str):
    """Run this file as a child with the compiler's dumps on; the first
    dumped file that matches, and the directory to remove."""
    where = tempfile.mkdtemp(prefix="kernel_bundles_")
    env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
        f"--xla_jf_dump_to={where} --xla_jf_dump_llo_text=true"))
    subprocess.run([sys.executable, __file__] + child_args, env=env,
                   capture_output=True)   # (a later dump may abort it)
    found = [f for f in glob.glob(os.path.join(where, pattern))
             if "schedule-analysis" not in f]
    return (found[0] if found else None), where


def read(path: str, window: int = 500) -> dict:
    rows = []
    for line in open(path):
        parts = line.split()
        if len(parts) == len(UNITS) and all(p.isdigit() for p in parts):
            rows.append([int(p) for p in parts])
    rows = rows[1:]                      # the first such line is the capacity
    return {"bundles": len(rows),
            "slots": {u: sum(r[i] for r in rows) for i, u in enumerate(UNITS)},
            "windows": [[at] + [sum(r[i] for r in rows[at:at + window])
                                for i in (0, 1, 2, 3)]
                        for at in range(0, len(rows), window)]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", type=int, nargs="+", default=[2])
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--child", type=int)
    ap.add_argument("--select", type=int, nargs="*")
    args = ap.parse_args(argv)
    if args.child and args.select is not None:
        _compile_select(args.child)
        return 0
    if args.child:
        _compile(args.child, args.f32)
        return 0
    if args.select is not None:
        for L in args.select or [67584, 68608]:
            path, where = _dumped(
                ["--select", "--child", str(L)],
                "*dsa_select*final_hlo-static-per-bundle-utilization.txt")
            line = {"kernel": "dsa_select", "rows": 32, "L": L, "k": 2048}
            if path:
                line.update(read(path), loops=loops(glob.glob(os.path.join(
                    where, "*dsa_select*[0-9]-final_bundles.txt"))[0]))
            else:
                line["error"] = "the kernel's schedule was not dumped"
            print(json.dumps(line), flush=True)
            shutil.rmtree(where, ignore_errors=True)
        return 0
    for pairs in args.pairs:
        path, where = _dumped(
            ["--child", str(pairs)] + (["--f32"] if args.f32 else []),
            "*_kda_chunk_call*final_hlo-static-per-bundle-utilization.txt")
        line = {"pairs_a_step": pairs, "f32": args.f32}
        if path:
            got = read(path)
            line.update(bundles_a_pair=got["bundles"] / pairs, **got,
                        windows_are="[first bundle, MXU, XLU, VALU, EUP]")
        else:
            line["error"] = "the kernel's schedule was not dumped"
        print(json.dumps(line), flush=True)
        shutil.rmtree(where, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
