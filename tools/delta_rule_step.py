"""What the gated delta rule costs alone on the chip, both forms.

    chiprun --chips 1 -- python3 tools/delta_rule_step.py [--f32] [--kda]
    chiprun --chips 1 -- python3 tools/delta_rule_step.py --tails [--f32]

At the ``serve.olmohybrid-sessions24`` cell's sizes (30 heads, keys of 96,
values of 192; ``ops/delta_rule.py``), or with ``--kda`` at the
``serve.solaropen2-reason128`` cell's (64 heads of 128 x 128, a decay a
CHANNEL of the key, 128 live rows of 193 slots, three layers; each form's
``max_abs_diff`` from the token-by-token recurrence is printed too):

- **the decode update**: 24 live rows of 53 slots, the slab ``(slots, 96,
  5760)`` float32 updated in place. ``pallas`` is the package's kernel,
  ``gather`` the same arithmetic on a gathered copy that XLA fuses as it
  likes; each timed as twelve calls (a model's twelve linear layers)
  chained in one program over one donated slab, in microseconds a live row
  and call, beside the least the bytes allow (a row's state read and
  written once at the memory peak);
- **the chunked form**: a chunk of 512 tokens (and of 1024) in blocks of
  64, bfloat16 operands (``--f32``: float32), twelve calls chained in one
  program, in nanoseconds a token and call, beside its flops at the bf16
  peak and its bytes at the memory peak
  (``benchmarks/costs_olmohybrid.py``); and the triangular system alone
  (the solve of ``(I + A) X = [V | K]``), to see its share. With ``--kda``
  in their place: a chunk of 512 positions of which 512, 320 and 64 are
  tokens, one line a count with the package's KERNEL
  (``delta_rule.delta_chunk_scan``, which skips the blocks past the count)
  beside XLA's form (``delta_rule._delta_chunk_scan_xla``, which computes
  every position), three layers chained: milliseconds a chunk and layer,
  nanoseconds a VALID token and layer beside the least its bytes allow,
  and each form's ``max_abs_diff_from_the_recurrence``.

With ``--tails`` it times, in the other measurements' place, **the
convolution's decode step alone** (``ops/ssm.py``: every live row's tail
advanced by one token) at the three cells that keep a tail beside a state:
Solar-Open2 (128 live rows of 193 slots, 3 x 24576 values a row, three
layers), Olmo-Hybrid (24 of 53, 3 x 11520, twelve) and Falcon-H1 (64 of 65,
3 x 5120, six), bfloat16 (``--f32``: float32). ``pallas`` is the package's
kernel (a row's slot read and written once, in place), ``gather`` the same
arithmetic on a gathered copy, both on the slab as the pool keeps it
(:func:`~marlin_tpu.ops.ssm.tail_slot_shape`); ``gather.rows`` is the form
the programs ran until PR 52, the slab ``(slots, taps - 1, channels)``.
Each is the slope between programs of 32 and of 96 passes over a model's
layers chained on one donated slab (a pass alone, 0.03-1 ms, reads the host's
dispatch; every step's output is summed, so no form can leave it out), in
microseconds a live row and layer beside the least the tail's bytes allow
(read and written once at the memory peak); ``ops_over_slab`` counts by name
the operations of a pass whose output is as large as the slab (a ``copy`` is
a copy of all of it).

Prints one JSON line a measurement and ends with ``{"ok": true, "device":
...}``; needs a TPU (a time from the CPU's interpreter says nothing). No
engine, no model."""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

HBM, FLOPS = 819e9, 197e12


def _recurrence(q, k, v, g, b, s):
    """The three lines a token at a time, float32 at the highest precision:
    ``q``, ``k`` (T, H, K), ``v`` (T, H, V), ``g`` (T, H[, K]), ``b`` (T,
    H), ``s`` (H, K, V). Returns ``(o (T, H, V), s)``."""
    import jax
    import jax.numpy as jnp

    def step(s, tok):
        q_t, k_t, v_t, g_t, b_t = tok
        a = jnp.exp(g_t)
        s = s * (a[..., None] if a.ndim == 2 else a[:, None, None])
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t,
                                             precision="highest"))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision="highest")

    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    s, o = jax.lax.scan(step, f32(s), tuple(f32(x) for x in (q, k, v, g, b)))
    return o, s


def _unit(x):
    import jax.numpy as jnp

    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def _best(fn, args, repeats: int = 5) -> float:
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*(out[:1] + args[1:]) if isinstance(out, tuple) else args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best


def _kda_chunks(cfg, peaks, least_scan, cd, ks, sizes) -> None:
    """A chunk of 512 positions of which 512, 320 and 64 are tokens: the
    package's kernel (``delta_chunk_scan`` at these sizes) beside XLA's form
    (``_delta_chunk_scan_xla``, which computes every position whatever the
    count), one line a count."""
    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops import delta_rule

    H, K, V, LAYERS = sizes
    T = 512
    q = (_unit(jax.random.normal(ks[1], (T, H, K))) * K ** -0.5).astype(cd)
    k = _unit(jax.random.normal(ks[2], (T, H, K))).astype(cd)
    v = jax.random.normal(ks[3], (T, H, V)).astype(cd)
    g = -jax.random.uniform(ks[4], (T, H, K), minval=0.001, maxval=0.1)
    b = jax.random.uniform(ks[5], (T, H), minval=0.0, maxval=2.0)
    s0 = jax.random.normal(ks[6], (K, H, V), jnp.float32)
    forms = {
        "kernel": lambda *a, valid: delta_rule.delta_chunk_scan(
            *a, block=64, valid=valid, interpret=False),
        "xla": lambda *a, valid: delta_rule._delta_chunk_scan_xla(
            *a, block=64)}
    for valid in (512, 320, 64):
        live = (jnp.arange(T) < valid)
        gv, bv = g * live[:, None, None], b * live[:, None]
        want, want_s = _recurrence(q[:valid], k[:valid], v[:valid],
                                   gv[:valid], bv[:valid],
                                   jnp.moveaxis(s0, 1, 0))
        least = least_scan(valid, 1, cfg, peaks)
        line = {"what": "chunk_scan", "positions": T, "valid": valid,
                "dtype": str(jnp.dtype(cd)), "layers": LAYERS,
                "least_ns_a_valid_token": 1e9 * least["seconds"] / valid,
                "bound": least["bound"],
                "output_scale": float(jnp.abs(want).max())}
        for name, form in forms.items():
            @jax.jit
            def scan(s, q, k, v, g, b, n, form=form):
                acc = 0.0
                for i in range(LAYERS):   # (scaled a layer: nothing shared)
                    o, s = form(q, k, v, g * (1.0 + 0.01 * i), b, s, valid=n)
                    acc = acc + o
                return s, acc

            n = jnp.int32(valid)
            seconds = _best(scan, (s0, q, k, v, gv, bv, n))
            o, s1 = jax.jit(form)(q, k, v, gv, bv, s0, valid=n)
            ns = 1e9 * seconds / (LAYERS * valid)
            line[name] = {
                "ms_a_chunk_and_layer": 1e3 * seconds / LAYERS,
                "ns_a_valid_token_and_layer": ns,
                "roofline_pct": 100 * 1e9 * least["seconds"] / valid / ns,
                "max_abs_diff_from_the_recurrence":
                float(jnp.abs(o[:valid] - want).max()),
                "state_max_abs_diff": float(jnp.abs(
                    jnp.moveaxis(s1, 1, 0) - want_s).max())}
        print(json.dumps(line), flush=True)


#: the tail step's three sizes: (live rows, slots, channels, layers)
TAILS = {"solaropen2": (128, 193, 24576, 3),
         "olmohybrid": (24, 53, 11520, 12),
         "falconh1": (64, 65, 5120, 6)}
TAPS = 4
#: passes over a model's layers in the two programs a slope is read from
PASSES = (32, 96)


def _tails(cd, tiny: bool) -> None:
    """One line a size and form (the module's text)."""
    import collections
    import functools
    import re

    import jax
    import jax.numpy as jnp

    from marlin_tpu.ops import ssm

    def rows_form(slab, slots, u, w, b):
        out, t1 = ssm.conv_step(u, slab[slots], w, b)
        return out, slab.at[slots].set(t1)

    forms = {
        "pallas": functools.partial(ssm.conv_step_slots, kernel="pallas",
                                    interpret=False if not tiny else None),
        "gather": functools.partial(ssm.conv_step_slots, kernel="gather"),
        "gather.rows": rows_form}
    for name, (rows, nslots, ch, layers) in TAILS.items():
        if tiny:
            rows, nslots, ch = 4, 7, ch // 64 // 128 * 128 or 128
        ks = jax.random.split(jax.random.key(1), 4)
        tails = jax.random.normal(ks[0], (nslots, TAPS - 1, ch)).astype(cd)
        slots = jnp.arange(1, rows + 1, dtype=jnp.int32)
        u = jax.random.normal(ks[1], (rows, ch)).astype(cd)
        w = jax.random.normal(ks[2], (TAPS, ch)).astype(cd)
        b = jax.random.normal(ks[3], (ch,)).astype(cd)
        least = 2.0 * (TAPS - 1) * ch * jnp.dtype(cd).itemsize / HBM
        want = None
        for form, step in forms.items():
            slab = (tails if form == "gather.rows" else
                    ssm.tails_slots(tails, TAPS, ch))

            @functools.partial(jax.jit, donate_argnums=(0,))
            def chain(slab, passes, slots, u, w, b, step=step):
                def one_pass(_, carry):
                    slab, acc = carry
                    for _ in range(layers):
                        out, slab = step(slab, slots, u, w, b)
                        acc = acc + out.sum()   # (every output is read once)
                    return slab, acc

                return jax.lax.fori_loop(0, passes, one_pass, (slab, 0.0))

            args = (slots, u, w, b)
            text = chain.lower(slab, 1, *args).compile().as_text()
            whole = re.escape("[" + ",".join(map(str, slab.shape)) + "]")
            over = dict(collections.Counter(
                m.group(1) for m in re.finditer(
                    r"= \w+" + whole + r"\S* ([\w\-]+)\(", text)
                if m.group(1) not in ("parameter", "bitcast", "tuple",
                                      "get-tuple-element", "while")))
            seconds = {passes: _best(chain, (jnp.array(slab), passes) + args)
                       for passes in PASSES}
            # (donated, as every program donates its slabs: XLA's memory
            # assignment aborts on a slab held to HBM that it has to copy)
            out1, slab1 = jax.jit(step, donate_argnums=(0,))(
                jnp.array(slab), *args)
            got = (out1, slab1 if form == "gather.rows" else
                   ssm.slot_tails(slab1, TAPS, ch))
            want = got if want is None else want
            us = 1e6 * (seconds[PASSES[1]] - seconds[PASSES[0]]) / (
                (PASSES[1] - PASSES[0]) * layers * rows)
            print(json.dumps({
                "what": "tail_step", "cell": name, "form": form,
                "dtype": str(jnp.dtype(cd)), "rows": rows, "slots": nslots,
                "channels": ch, "layers": layers,
                "us_a_live_row_and_layer": us, "least_us": 1e6 * least,
                "roofline_pct": 100 * 1e6 * least / us if us > 0 else None,
                "ms_a_step_of_the_layers": 1e-3 * us * layers * rows,
                "ms_the_programs": [1e3 * seconds[p] for p in PASSES],
                "ops_over_slab": over,
                "max_abs_diff_out": float(jnp.abs(
                    got[0] - want[0]).max()),
                "tails_equal": bool((got[1] == want[1]).all())}),
                flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--f32", action="store_true")
    ap.add_argument("--kda", action="store_true")
    ap.add_argument("--tails", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="--tails at a small size on a CPU: the control "
                    "flow alone (ends non-zero)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from benchmarks import costs_olmohybrid, costs_solaropen2
    from marlin_tpu.ops import delta_rule

    dev = jax.devices()[0]
    if args.tails and (args.tiny or dev.platform == "tpu"):
        _tails(jnp.float32 if args.f32 else jnp.bfloat16, args.tiny)
        print(json.dumps({"ok": dev.platform == "tpu",
                          "device": dev.device_kind}))
        return 0 if dev.platform == "tpu" else 1
    if dev.platform != "tpu":
        print(json.dumps({"ok": False, "why": "not a TPU"}))
        return 1
    if args.kda:
        H, K, V, ROWS, SLOTS, LAYERS = 64, 128, 128, 128, 193, 3
        costs, least_scan = costs_solaropen2, \
            costs_solaropen2.kda_prefill_least_seconds
        cfg = {"linear_attn_config": {"num_heads": H, "head_dim": K,
                                      "short_conv_kernel_size": 4},
               "kda_chunk_size": 64, "num_hidden_layers": 1,
               "gqa_layers": []}
    else:
        H, K, V, ROWS, SLOTS, LAYERS = 30, 96, 192, 24, 53, 12
        costs, least_scan = costs_olmohybrid, \
            costs_olmohybrid.gdn_prefill_least_seconds
        cfg = {"linear_num_key_heads": H, "linear_key_head_dim": K,
               "linear_value_head_dim": V, "linear_conv_kernel_dim": 4,
               "linear_chunk_size": 64, "num_hidden_layers": 1,
               "layer_types": ["linear_attention"]}
    cfg["compute_dtype"] = "float32" if args.f32 else "bfloat16"
    decays = (H, K) if args.kda else (H,)   # one a channel / one a head
    peaks = {"hbm_bytes_per_s": HBM, "bf16_flops_per_s": FLOPS}
    cd = jnp.float32 if args.f32 else jnp.bfloat16
    ks = jax.random.split(jax.random.key(0), 8)

    unit = _unit

    # ---- the decode update
    slab = jax.random.normal(ks[0], (SLOTS, K, H * V), jnp.float32)
    slots = jnp.arange(1, ROWS + 1, dtype=jnp.int32)
    q = unit(jax.random.normal(ks[1], (ROWS, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[2], (ROWS, H, K)))
    v = jax.random.normal(ks[3], (ROWS, H, V))
    g = -jax.random.uniform(ks[4], (ROWS,) + decays, minval=0.001,
                            maxval=0.1)
    b = jax.random.uniform(ks[5], (ROWS, H), minval=0.0, maxval=2.0)
    least = 2.0 * costs.state_bytes(cfg) / HBM
    outs = {}
    for kernel in ("pallas", "gather"):
        @jax.jit
        def chain(slab, slots, q, k, v, g, b, kernel=kernel):
            acc = 0.0
            for i in range(LAYERS):   # (scaled a layer: nothing is shared)
                slab, o = delta_rule.delta_decode_update(
                    slab, slots, q, k * (1.0 - 0.01 * i), v, g, b,
                    kernel=kernel, interpret=False)
                acc = acc + o
            return slab, acc

        fn = jax.jit(chain, donate_argnums=(0,))
        seconds = _best(fn, (jnp.array(slab), slots, q, k, v, g, b))
        outs[kernel] = fn(jnp.array(slab), slots, q, k, v, g, b)[1]
        us = 1e6 * seconds / (LAYERS * ROWS)
        print(json.dumps({
            "what": "decode_update", "kernel": kernel, "rows": ROWS,
            "us_a_live_row_and_layer": us,
            "least_us": 1e6 * least, "roofline_pct": 100 * 1e6 * least / us,
            "layers": LAYERS, "ms_a_step": 1e3 * seconds}), flush=True)
    # one layer's update of every live row, by the recurrence's one step
    want = jax.vmap(lambda s, *tok: _recurrence(
        *(x[None] for x in tok), s)[0][0])(
            delta_rule.slab_to_state(slab[slots], H), q, k, v, g, b)
    one = {kernel: delta_rule.delta_decode_update(
        jnp.array(slab), slots, q, k, v, g, b, kernel=kernel,
        interpret=False)[1] for kernel in outs}
    print(json.dumps({"what": "decode_update", "max_abs_diff_pallas_gather":
                      float(jnp.abs(outs["pallas"] - outs["gather"]).max()),
                      "max_abs_diff_from_the_recurrence": {
                          kernel: float(jnp.abs(o - want).max())
                          for kernel, o in one.items()},
                      "output_scale": float(jnp.abs(outs["gather"]).max())}),
          flush=True)

    # ---- the chunked form
    if args.kda:
        _kda_chunks(cfg, peaks, least_scan, cd, ks, (H, K, V, LAYERS))
        print(json.dumps({"ok": True, "device": dev.device_kind}))
        return 0
    for T in (512, 1024):
        q = (unit(jax.random.normal(ks[1], (T, H, K))) * K ** -0.5).astype(cd)
        k = unit(jax.random.normal(ks[2], (T, H, K))).astype(cd)
        v = jax.random.normal(ks[3], (T, H, V)).astype(cd)
        g = -jax.random.uniform(ks[4], (T,) + decays, minval=0.001,
                                maxval=0.1)
        b = jax.random.uniform(ks[5], (T, H), minval=0.0, maxval=2.0)
        s0 = jax.random.normal(ks[6], (K, H, V), jnp.float32)

        @jax.jit
        def scan(s, q, k, v, g, b):
            acc = 0.0
            for i in range(LAYERS):   # (scaled a layer: nothing is shared)
                o, s = delta_rule.delta_chunk_scan(
                    q, k, v, g * (1.0 + 0.01 * i), b, s, block=64)
                acc = acc + o
            return s, acc

        seconds = _best(scan, (s0, q, k, v, g, b))
        least = least_scan(T, 1, cfg, peaks)
        ns = 1e9 * seconds / (LAYERS * T)
        o, _ = delta_rule.delta_chunk_scan(q, k, v, g, b, s0, block=64)
        want, _ = _recurrence(q, k, v, g, b, jnp.moveaxis(s0, 1, 0))
        print(json.dumps({
            "what": "chunk_scan", "tokens": T, "dtype": str(jnp.dtype(cd)),
            "ns_a_token_and_layer": ns,
            "least_ns": 1e9 * least["seconds"] / T, "bound": least["bound"],
            "roofline_pct": 100 * 1e9 * least["seconds"] / T / ns,
            "layers": LAYERS, "ms_a_chunk": 1e3 * seconds,
            "max_abs_diff_from_the_recurrence":
            float(jnp.abs(o - want).max()),
            "output_scale": float(jnp.abs(want).max())}), flush=True)
        nc, C = T // 64, 64
        A = jnp.tril(jax.random.normal(ks[7], (nc, H, C, C)) * 0.1, -1)
        rhs = jax.random.normal(ks[6], (nc, H, C, V + K), jnp.float32)

        @jax.jit
        def solve(A, rhs):
            acc = 0.0
            for i in range(LAYERS):
                acc = acc + jax.scipy.linalg.solve_triangular(
                    jnp.eye(C) + A * (1.0 + 0.01 * i), rhs, lower=True,
                    unit_diagonal=True)
            return acc

        @jax.jit
        def blocked(A, rhs):
            acc = 0.0
            for i in range(LAYERS):
                acc = acc + jnp.matmul(
                    delta_rule._unit_lower_inverse(A * (1.0 + 0.01 * i))
                    .astype(cd), rhs.astype(cd),
                    preferred_element_type=jnp.float32)
            return acc

        for name, fn in (("xla_triangular_solve", solve),
                         ("blocked_inverse", blocked)):
            seconds = _best(fn, (A, rhs))
            print(json.dumps({"what": "solve_alone", "how": name,
                              "tokens": T, "ns_a_token_and_layer":
                              1e9 * seconds / (LAYERS * T)}), flush=True)
        print(json.dumps({"what": "solve_alone", "max_abs_diff": float(
            jnp.abs(solve(A, rhs) - blocked(A, rhs)).max())}), flush=True)
    print(json.dumps({"ok": True, "device": dev.device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
