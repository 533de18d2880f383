"""Smoke tests for the example CLIs (the reference's examples are its manual
integration suite — SURVEY.md §4; here they run in-process on the CPU mesh)
and the C++ genmat tool."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import marlin_tpu as mt


def test_matrix_multiply_cli(capsys):
    from examples.matrix_multiply import main

    main(["64", "48", "32", "8"])
    out = capsys.readouterr().out
    assert "used time" in out and "GFLOP/s" in out


def test_matrix_multiply_cli_files(tmp_path, capsys, mesh):
    a = np.random.default_rng(0).random((12, 12)).astype(np.float32)
    b = np.random.default_rng(1).random((12, 12)).astype(np.float32)
    pa, pb = str(tmp_path / "a.txt"), str(tmp_path / "b.txt")
    mt.DenseVecMatrix.from_array(a, mesh).save_to_file_system(pa)
    mt.DenseVecMatrix.from_array(b, mesh).save_to_file_system(pb)
    from examples.matrix_multiply import main

    c = main(["--files", pa, pb])
    np.testing.assert_allclose(c.to_numpy(), a @ b, rtol=1e-4, atol=1e-4)


def test_blas1_cli(capsys):
    from examples.blas1 import main

    for mode in ("local", "dist"):
        main([mode, "100", "4"])
    assert "inner product" in capsys.readouterr().out


def test_blas3_cli(capsys):
    from examples.blas3 import main

    for mode_args in (["32", "32", "32", "1"], ["32", "32", "32", "2"],
                      ["32", "32", "32", "3", "2", "2", "2"]):
        main(mode_args)
    out = capsys.readouterr().out
    assert "local multiply" in out and "broadcast multiply" in out and "rmm multiply" in out


def test_rmm_compare_tuned_mode(capsys):
    from examples.rmm_compare import main

    timings = main(["48", "32", "24", "tuned"])
    out = capsys.readouterr().out
    assert "fastest:" in out and len(timings) >= 2


def test_rmm_compare_cli(capsys):
    from examples.rmm_compare import main

    timings = main(["48", "48", "48", "all"])
    assert set(timings) == {"rmm", "gspmd", "broadcast"}
    assert "fastest:" in capsys.readouterr().out


def test_sparse_multiply_cli(capsys):
    from examples.sparse_multiply import main

    for mode in "1234567":
        main(["32", "32", "32", "0.1", mode])
    out = capsys.readouterr().out
    assert "millis" in out


def test_lu_example_cli(tmp_path, capsys, mesh):
    n = 12
    a = np.random.default_rng(0).random((n, n)).astype(np.float32) + n * np.eye(n, dtype=np.float32)
    p = str(tmp_path / "a.txt")
    mt.DenseVecMatrix.from_array(a, mesh).save_to_file_system(p)
    from examples.lu_decompose import main

    main([p, str(n), str(n), str(tmp_path / "out")])
    out = capsys.readouterr().out
    assert "LU used" in out
    l = mt.load_matrix_file(str(tmp_path / "out.L"), mesh).to_numpy()
    u = mt.load_matrix_file(str(tmp_path / "out.U"), mesh).to_numpy()
    perm = [int(x) for x in open(str(tmp_path / "out.perm")).read().split(",")]
    np.testing.assert_allclose(a[perm], l @ u, rtol=1e-3, atol=1e-3)


def test_lr_cli(capsys):
    from examples.logistic_regression import main

    main(["50", "10.0", "500", "10"])
    out = capsys.readouterr().out
    assert "accuracy" in out


def test_pagerank_cli(tmp_path, capsys):
    p = tmp_path / "edges.txt"
    p.write_text("0 1\n1 2\n2 0\n2 1\n")
    from examples.pagerank import main

    main([str(p), "30"])
    out = capsys.readouterr().out
    assert "node" in out


def test_als_cli(tmp_path, capsys):
    rng = np.random.default_rng(0)
    lines = [f"{u} {i} {rng.random() * 5:.3f}" for u in range(20) for i in range(10)
             if rng.random() < 0.5]
    p = tmp_path / "ratings.txt"
    p.write_text("\n".join(lines))
    from examples.als import main

    main([str(p), "3", "5", "0.1"])
    out = capsys.readouterr().out
    assert "RMSE" in out


def test_nn_cli(capsys):
    from examples.neural_network import main

    main(["synthetic", "-", "30", "16", "1.0", "128"])
    out = capsys.readouterr().out
    assert "train accuracy" in out


def test_long_context_training_cli(capsys):
    from examples.long_context_training import main

    losses = main(["128", "6", "32", "4", "1"])
    out = capsys.readouterr().out
    assert "tok/s" in out and losses[-1] < losses[0]
    assert "greedy continuation" in out


def test_pipeline_training_cli(capsys):
    from examples.pipeline_training import main

    losses = main(["8", "65", "6", "32", "4", "2"])
    out = capsys.readouterr().out
    assert "stages" in out and "tok/s" in out
    assert losses[-1] < losses[0]


def test_moe_training_cli(capsys):
    from examples.moe_training import main

    losses = main(["192", "8", "4", "2", "32", "1"])
    out = capsys.readouterr().out
    assert "load-balance aux" in out and "tok/s" in out
    assert losses[-1] < losses[0]
    assert "greedy continuation" in out


def test_long_context_training_cli_chunked(capsys):
    from examples.long_context_training import main

    # remat + chunked head (the lct_long combination), chunk ∤ seq-1
    losses = main(["128", "6", "32", "4", "1", "ring", "1", "48"])
    out = capsys.readouterr().out
    assert "loss_chunk=48" in out and losses[-1] < losses[0]


@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_attention_cli(capsys, strategy):
    from examples.attention import main

    main(["64", "16", "1", "4", strategy])
    out = capsys.readouterr().out
    assert strategy in out and "GFLOP/s" in out


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_genmat_tool(tmp_path, mesh):
    import os

    build = subprocess.run(
        ["make", "-C", os.path.join(os.path.dirname(__file__), "..", "tools")],
        capture_output=True, text=True,
    )
    assert build.returncode == 0, build.stderr
    exe = os.path.join(os.path.dirname(__file__), "..", "tools", "genmat")
    out = subprocess.run([exe, "5", "4", "7"], capture_output=True, text=True)
    assert out.returncode == 0
    path = tmp_path / "gen.txt"
    path.write_text(out.stdout)
    m = mt.load_matrix_file(str(path), mesh)
    arr = m.to_numpy()
    assert arr.shape == (5, 4)
    assert (arr >= 0).all() and (arr < 5).all()
    # deterministic per seed
    again = subprocess.run([exe, "5", "4", "7"], capture_output=True, text=True)
    assert again.stdout == out.stdout
    other = subprocess.run([exe, "5", "4", "8"], capture_output=True, text=True)
    assert other.stdout != out.stdout


def test_distributed_training_cli(capsys, tmp_path):
    from examples.distributed_training import main

    main(["60", "16", "64", str(tmp_path / "ckpt")])
    out = capsys.readouterr().out
    assert "data-parallel" in out and "accuracy" in out


def test_decode_serving_cli(capsys):
    from examples.decode_serving import main

    outs = main(["3", "12", "4", "32", "2", "1"])
    assert len(outs) == 3
    out = capsys.readouterr().out
    assert "batched" in out and "one-at-a-time" in out


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_chip_entry_points_refuse_a_cpu(script):
    """The chip entry points measure nothing without a TPU: on the CPU they
    exit non-zero, say why, and print no result line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(root, script)],
                          capture_output=True, text=True, env=env, cwd=root,
                          timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert proc.stdout.strip() == ""


# ----------------------------------------------------- `make check`'s gates

GATES = ["serve-gate", "ooc-gate", "kernel-gate", "obs-gate", "fleet-gate",
         "program-gate", "mem-gate"]


@pytest.mark.parametrize("gate", GATES)
def test_a_gate_is_pytest_over_files_that_exist(gate):
    """Each ``*-gate`` target of tools/Makefile is a pytest run of tier-1
    suites and nothing else (no timing is diffed there: speed is read on the
    chip by benchmarks/), every file it names is in the tree, and ``check``
    runs it."""
    if shutil.which("make") is None:
        pytest.skip("no make")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def dry(target):
        proc = subprocess.run(["make", "-C", "tools", "-n", target],
                              capture_output=True, text=True, cwd=root,
                              timeout=60)
        assert proc.returncode == 0, proc.stderr
        return [ln for ln in proc.stdout.replace("\\\n", " ").splitlines()
                if ln.strip() and not ln.startswith("make")]

    commands = dry(gate)
    assert len(commands) == 1, commands
    words = commands[0].split()
    assert words[:3] == ["cd", "..", "&&"] and "JAX_PLATFORMS=cpu" in words
    assert words[words.index("-m"):][:2] == ["-m", "pytest"]
    suites = [w for w in words if w.startswith("tests/")]
    assert len(suites) >= 2 and len(set(suites)) == len(suites)
    for path in suites:
        assert os.path.isfile(os.path.join(root, path)), path
    assert "'not slow'" in commands[0]
    assert commands[0] in dry("check")


def test_make_check_is_the_analyzer_the_gates_and_the_report_smoke():
    """The root ``make check`` builds the native libraries and hands over to
    tools/Makefile's ``check``: the analyzer's selftest and gate, the seven
    pytest gates, the obs-report smoke over the checked-in log, and no
    command that diffs a timing."""
    if shutil.which("make") is None:
        pytest.skip("no make")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(["make", "-n", "check"], capture_output=True,
                          text=True, cwd=root, timeout=60)
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout.replace("\\\n", " ")
    lines = [ln for ln in out.splitlines() if ln.startswith("cd .. && ")]
    assert sum("-m pytest" in ln for ln in lines) == len(GATES)
    assert [ln for ln in lines if "-m pytest" not in ln] == [
        "cd .. && python -m tools.analyze --selftest",
        "cd .. && python -m tools.analyze",
        "cd .. && python -m marlin_tpu.obs.report "
        "tools/fixtures/obs_events.jsonl"]
    assert "marlin_tpu/native" in out and "bench" not in out.lower()
