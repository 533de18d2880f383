#!/usr/bin/env python
"""Compiler-verified HBM accounting for the long-context configs.

AOT-compiles the SAME jitted programs ``chip_smoke.py``'s long-context phase
executes — `lm_train_step` (ring flash attention + remat + chunked LM
head) and the ring flash forward — against a compile-only v5e topology
(utils/aot.py: libtpu, no chip), and records the TPU compiler's own
memory analysis per sequence length into AOT_MEMORY.json.

This is the evidence channel for the docs/parallelism.md HBM budget table:
the "compiler-verified" peak replaces hand arithmetic wherever the two
disagree. Run on-chip benches remain the throughput source of truth; this
tool proves *feasibility* (fits in 16 GB) and kernel *compilability* ahead
of chip time.

Usage: python tools/aot_report.py [seq ...]   (defaults: 262144 524288 1048576)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # compile-only: never a chip

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import marlin_tpu as mt  # noqa: E402
from marlin_tpu.models.transformer import TransformerLM  # noqa: E402
from marlin_tpu.parallel.ring_attention import ring_attention  # noqa: E402
from marlin_tpu.utils.aot import topology_mesh  # noqa: E402

GIB = 1024 ** 3
V5E_HBM = 16 * GIB


def _usable_budget() -> int:
    """Raw HBM minus the documented reserve — the same policy plan_context
    applies (round-4 verdict #2: a 'fits' against the 16 GiB sticker can
    still OOM on chip)."""
    from marlin_tpu.models.planner import usable_hbm_bytes

    return usable_hbm_bytes(V5E_HBM)


def _mem(compiled):
    ma = compiled.memory_analysis()
    out = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "peak_bytes": ma.peak_memory_in_bytes,
        "peak_gib": round(ma.peak_memory_in_bytes / GIB, 3),
        "fits_16gib": ma.peak_memory_in_bytes < V5E_HBM,
        "fits_usable_hbm": ma.peak_memory_in_bytes < _usable_budget(),
    }
    host = getattr(ma, "host_temp_size_in_bytes", 0)
    if host:  # offloaded residuals live here, not in device HBM
        out["host_temp_bytes"] = host
    return out


def lct_train_step(seq: int, mesh, compute_dtype=None,
                   offload: bool = False, mlp_chunk=None,
                   n_experts=None, moe_group=8192) -> dict:
    """AOT-compile one lct_long training step (same knobs as config_lct_long:
    d256/h2/l2/v512, remat, loss_chunk=16k, ring_flash; optionally the bf16
    activation path, host-offloaded residuals, the chunked FFN, or the MoE
    FFN — ``n_experts`` swaps in grouped GShard top-2 routing + Switch aux,
    the row proving expert routing keeps long-context memory linear in
    seq)."""
    from marlin_tpu.utils.aot import trace_lm_train_step

    lm = TransformerLM(vocab=512, d_model=256, heads=2, layers=2,
                      attn="ring_flash", remat=True, loss_chunk=16384,
                      compute_dtype=compute_dtype, mlp_chunk=mlp_chunk,
                      offload_residuals=offload, n_experts=n_experts,
                      moe_group=moe_group)
    t0 = time.time()
    with mt.config_context(pallas_interpret=False):
        compiled = trace_lm_train_step(lm, seq, mesh).lower().compile()
    out = _mem(compiled)
    out["compile_s"] = round(time.time() - t0, 1)
    return out


def moe_train_step(seq: int, mesh) -> dict:
    """The MoE row of the report (docs/parallelism.md "Expert
    parallelism"): the shared lct recipe with 8 experts."""
    return lct_train_step(seq, mesh, n_experts=8)


def attn_forward(seq: int, mesh) -> dict:
    """AOT-compile the attn_long flash forward (d=128 head)."""
    rep = NamedSharding(mesh, P())
    a = jax.ShapeDtypeStruct((seq, 128), jnp.float32, sharding=rep)
    t0 = time.time()
    with mt.config_context(pallas_interpret=False):
        compiled = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=True,
                                           backend="flash"),
        ).trace(a, a, a).lower().compile()
    out = _mem(compiled)
    out["compile_s"] = round(time.time() - t0, 1)
    return out


# bf16-only escalations past the f32 cliff: these run ONLY on the bf16 sweep
# (their f32 compiles are known-doomed hour-long OOMs) and are part of the
# default run so a plain `python tools/aot_report.py` regenerates every
# number the docs cite.
BF16_EXTRA_SEQS = [1572864, 2097152]

_REPORT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "AOT_MEMORY.json")


def main(seqs):
    mesh = topology_mesh(("rows",), (1,))  # the single-chip bench shape
    # merge-update: a partial rerun (subset of seqs) must refresh its rows
    # without dropping the rest of the committed evidence
    try:
        with open(_REPORT_PATH) as f:
            report = json.load(f)
    except (FileNotFoundError, ValueError):
        report = {}
    report["topology"] = "v5e (compile-only, libtpu " + _libtpu_version() + ")"
    report["usable_hbm_budget_bytes"] = _usable_budget()
    report["usable_hbm_note"] = (
        "fits_usable_hbm is keyed to 16 GiB minus a 0.75 GiB runtime "
        "reserve (models/planner.usable_hbm_bytes)")
    report["program"] = (
        "lm_train_step d256/h2/l2/v512 remat+loss_chunk16k "
        "ring_flash (the `lct_32768tok` row of PERF.md's r5 table) and the "
        "ring-flash causal forward at d=128 (its `ring_attention` rows)")
    for sec in ("lct_long", "lct_long_bf16", "attn_long", "lct_long_4chip"):
        report.setdefault(sec, {})
    for seq in seqs:
        print(f"[aot] lct_long seq={seq} ...", flush=True)
        report["lct_long"][str(seq)] = r = _try(lct_train_step, seq, mesh)
        print(f"  {_fmt(r)}", flush=True)
    for seq in list(seqs) + BF16_EXTRA_SEQS:
        print(f"[aot] lct_long_bf16 seq={seq} ...", flush=True)
        report["lct_long_bf16"][str(seq)] = r = _try(
            lambda s, m: lct_train_step(s, m, compute_dtype="bfloat16"),
            seq, mesh)
        print(f"  {_fmt(r)}", flush=True)
    # host-offloaded residuals + chunked FFN on top of bf16: the knobs that
    # push past the single-chip cliff (r4 verdict #5) — 1M as a sanity delta
    # vs plain bf16, then the 2M+ escalations
    report.setdefault("lct_long_bf16_offload", {})
    for seq in [1048576, 2097152, 3145728]:
        print(f"[aot] lct_long_bf16_offload seq={seq} ...", flush=True)
        report["lct_long_bf16_offload"][str(seq)] = r = _try(
            lambda s, m: lct_train_step(s, m, compute_dtype="bfloat16",
                                        offload=True, mlp_chunk=16384),
            seq, mesh)
        print(f"  {_fmt(r)}", flush=True)
    for seq in seqs:
        print(f"[aot] attn_long seq={seq} ...", flush=True)
        report["attn_long"][str(seq)] = r = _try(attn_forward, seq, mesh)
        print(f"  {_fmt(r)}", flush=True)
    # MoE at the first (256k-class) rung: expert routing must not bend the
    # linear-in-seq memory story
    report.setdefault("moe_long_e8", {})
    for seq in seqs[:1]:
        print(f"[aot] moe_long_e8 seq={seq} ...", flush=True)
        report["moe_long_e8"][str(seq)] = r = _try(moe_train_step, seq, mesh)
        print(f"  {_fmt(r)}", flush=True)
    # multi-chip: the budget table's "p chips train p× the context at the
    # same per-chip residency" claim, compiler-verified on a real 4-chip v5e
    # topology (ring over ICI). memory_analysis is per device.
    mesh4 = topology_mesh(("rows",), (4,), topology_name="v5e:2x2")
    for seq, cd in ((4 * seqs[-1], "bfloat16"), (seqs[-1], None)):
        label = f"{seq}{'_bf16' if cd else ''}"
        print(f"[aot] lct_long_4chip {label} ...", flush=True)
        report["lct_long_4chip"][label] = r = _try(
            lambda s, m: lct_train_step(s, m, compute_dtype=cd), seq, mesh4)
        print(f"  {_fmt(r)} (per chip)", flush=True)

    with open(_REPORT_PATH, "w") as f:
        json.dump(report, f, indent=2)
    print("wrote AOT_MEMORY.json")


def _try(fn, seq, mesh) -> dict:
    """An over-HBM configuration is a *result* (the compiler locating the
    cliff), not a tool crash: record the compiler's own accounting."""
    from marlin_tpu.utils.aot import parse_hbm_oom

    try:
        return fn(seq, mesh)
    except Exception as e:
        needed = parse_hbm_oom(e)
        return {
            "fits_16gib": False,
            "error": (f"compiler: needs {needed / GIB:.2f}G HBM"
                      if needed else str(e).split("\n")[0][:200]),
        }


def _fmt(r: dict) -> str:
    if "error" in r:
        return f"OVER HBM — {r['error']}"
    return (f"peak {r['peak_gib']} GiB, temps {r['temp_bytes'] / GIB:.3f} GiB, "
            f"compile {r['compile_s']}s")


def _libtpu_version() -> str:
    try:
        import libtpu
        return getattr(libtpu, "__version__", "?")
    except ImportError:
        return "?"


if __name__ == "__main__":
    seqs = [int(a) for a in sys.argv[1:]] or [262144, 524288, 1048576]
    main(seqs)
