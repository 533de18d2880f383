"""Each driver end to end on a CPU at a tiny size; the harness's refusals; a
broken timed path must come out as not correct; the lower-precision controls
must fail the comparisons that the sound paths pass."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RUN = os.path.join(ROOT, "benchmarks", "run.py")
BENCH = os.path.join(HERE, "rehearsal", "BENCHMARK.json")


def _env(devices=1):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _subprocess(args, devices=1, cwd=ROOT, script=RUN):
    return subprocess.run([sys.executable, script] + args, cwd=cwd,
                          env=_env(devices), capture_output=True, text=True,
                          timeout=600)


def _result(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,devices,trace", [
    ("matmul.tiny", 1, 0), ("matmul.tiny-mesh4", 4, 1),
    ("serve.tiny-closed", 1, 1), ("serve.tiny-open", 1, 0)])
def test_cpu_rehearsal_runs_each_driver_and_prints_no_device_metric(
        cell, devices, trace):
    p = _subprocess(["--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
                     cell, "--seed", "3000000123", "--seconds", "2",
                     "--trace", str(trace)], devices)
    assert p.returncode == 0, p.stderr[-2000:]
    r = _result(p.stdout)
    assert set(r) == {"correct", "attempted", "failed", "metrics", "device"}
    assert r["device"]["platform"] == "cpu" and r["metrics"] == {}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    notes = [json.loads(x) for x in p.stdout.strip().splitlines()[:-1]]
    window = next(n for n in notes if n["note"] == "window")
    assert window["compiles_in_window"] == 0 and window["setup_s"] > 0
    assert any(n["note"] == "compared" and "limit" in n for n in notes)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _subprocess(["--workload", "matmul.square-1chip", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and "needs a TPU" in p.stderr
    assert not p.stdout.strip() or "correct" not in p.stdout.splitlines()[-1]


def test_too_few_chips_fail():
    p = _subprocess(["--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
                     "matmul.tiny-mesh4", "--seed", "1", "--seconds", "1"], 1)
    assert p.returncode != 0 and "needs 4 chips" in p.stderr


def test_a_checkout_without_the_program_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _subprocess(["--workload", "matmul.square-1chip", "--seed", "1",
                     "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                    script=str(tmp_path / "benchmarks" / "run.py"))
    assert p.returncode != 0 and "correct" not in p.stdout


# ---- in this process: the rest of a run with the timed path broken underneath


def _main(capsys, cell, seed=11, seconds="1", extra=()):
    from benchmarks import run

    rc = run.main(["--bench", BENCH, "--allow-cpu-rehearsal", "--workload",
                   cell, "--seed", str(seed), "--seconds", seconds,
                   "--trace", "0", *extra])
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(lines[-1]), [json.loads(x) for x in lines[:-1]]


def test_sound_matmul_is_correct_in_process(capsys):
    rc, result, _ = _main(capsys, "matmul.tiny")
    assert rc == 0 and result["correct"] is True


def test_a_multiply_that_returns_its_operand_is_not_correct(
        capsys, monkeypatch):
    from marlin_tpu.matrix.dense import DenseMatrix

    monkeypatch.setattr(DenseMatrix, "multiply",
                        lambda self, other, **kw: self)
    rc, result, notes = _main(capsys, "matmul.tiny")
    assert rc == 0 and result["correct"] is False
    err = next(n for n in notes if n.get("name") == "rel_err_vs_float64")
    assert err["value"] > err["limit"]


def test_a_product_with_one_block_left_out_is_not_correct(
        capsys, monkeypatch):
    """Leave out one quarter of the product (a chip's block of a 2x2 mesh):
    the seeded sample falls on every block, so it is seen."""
    from marlin_tpu.matrix.dense import DenseMatrix

    sound = DenseMatrix.multiply

    def broken(self, other, **kw):
        c = sound(self, other, **kw)
        n = c.data.shape[0] // 2
        c.data = c.data.at[n:, n:].set(0.0)
        return c

    monkeypatch.setattr(DenseMatrix, "multiply", broken)
    rc, result, _ = _main(capsys, "matmul.tiny")
    assert rc == 0 and result["correct"] is False


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from marlin_tpu.models import transformer

    sound = transformer.lm_decode_paged

    def altered(params, *a, **kw):
        pages, nxt = sound(params, *a, **kw)
        return pages, (nxt + 1) % params["emb"].shape[0]

    monkeypatch.setattr(transformer, "lm_decode_paged", altered)
    rc, result, notes = _main(capsys, "serve.tiny-closed", seconds="2")
    assert rc == 0 and result["correct"] is False
    gap = next(n for n in notes if n.get("name") == "served_logit_gap")
    assert gap["value"] > gap["limit"]


def test_a_failed_request_is_not_correct(capsys, monkeypatch):
    """A request the engine refuses counts as failed, and the run as not
    correct, whatever the tokens of the others."""
    from marlin_tpu.serving import engine

    sound = engine.ServeEngine._submit
    seen = []

    def refusing(self, request, ctx):
        seen.append(request.rid)
        if len(seen) == 5:
            request.program = "no-such-program"
        return sound(self, request, ctx)

    monkeypatch.setattr(engine.ServeEngine, "_submit", refusing)
    rc, result, _ = _main(capsys, "serve.tiny-closed", seconds="2")
    assert rc == 0 and result["failed"] >= 1 and result["correct"] is False


# ---- the controls: the nearest lower precision, in the reference's place


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_one_bf16_pass_fails_the_matrix_comparison(seed):
    """float32 at ``high`` is what the configuration states; one bfloat16
    pass is the step below it."""
    import jax.numpy as jnp

    from benchmarks.reference import matmul as ref

    with open(os.path.join(HERE, "..", "configs",
                           "marlin-square-1chip.json")) as f:
        limit = json.load(f)["check"]["limits"]["rel_err_vs_float64"]
    rng = np.random.default_rng(seed)
    n, k = 4096, 64
    a = rng.uniform(-1, 1, (k, n)).astype(np.float32)
    b = rng.uniform(-1, 1, (n, k)).astype(np.float32)
    want = ref.product_sample(a, b)
    sound = a @ b
    low = np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32),
                     np.float64) @ np.asarray(
        jnp.asarray(b, jnp.bfloat16).astype(jnp.float32), np.float64)
    assert ref.rel_err(sound, want) < limit / 10
    assert ref.rel_err(low, want) > 3 * limit
    assert ref.rel_err(sound[:, :-1], want) == float("inf")


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_float8_fails_the_serving_comparison(capsys, seed):
    """bfloat16 activations are what the configuration states; float8
    operands are the step below. Same prompts, same served tokens."""
    rc, result, notes = _main(capsys, "serve.tiny-closed", seed=seed,
                              seconds="2", extra=("--control", "1"))
    assert rc == 0 and result["correct"] is True
    sound = next(n for n in notes if n.get("name") == "served_logit_gap")
    low = next(n for n in notes
               if n.get("name") == "control_served_logit_gap")
    assert sound["value"] < sound["limit"] < low["value"]
    assert low["value"] > 3 * sound["value"]
