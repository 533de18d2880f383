"""GPipe pipeline parallelism: schedule correctness and differentiability.

The oracle is the plain sequential composition of the stages — the pipeline
is purely an execution schedule, so its output (and gradients) must match
bit-for-bit-level tolerances on the CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu.parallel.pipeline import (pipeline_apply, split_microbatches,
                                          stack_stage_params)


def _mlp_stage(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _make_stages(key, n_stages, d):
    ks = jax.random.split(key, n_stages)
    return [
        {"w": jax.random.normal(k, (d, d), jnp.float32) / np.sqrt(d),
         "b": jnp.zeros((d,), jnp.float32)}
        for k in ks
    ]


def _sequential(per_stage, x):
    for p in per_stage:
        x = _mlp_stage(p, x)
    return x


@pytest.fixture
def mesh4():
    return mt.create_mesh((4, 2))


def test_pipeline_matches_sequential(mesh4):
    rng = np.random.default_rng(0)
    d, batch = 16, 24
    per_stage = _make_stages(jax.random.key(1), 4, d)
    x = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))
    stacked = stack_stage_params(per_stage, mesh4)
    out = pipeline_apply(stacked, _mlp_stage, x, mesh4, microbatch=4)
    ref = _sequential(per_stage, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_default_microbatch(mesh4):
    # default microbatch = batch // n_stages: still exact
    rng = np.random.default_rng(1)
    d, batch = 8, 8
    per_stage = _make_stages(jax.random.key(2), 4, d)
    x = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))
    out = pipeline_apply(stack_stage_params(per_stage, mesh4), _mlp_stage, x,
                         mesh4)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_sequential(per_stage, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_single_microbatch_many(mesh4):
    # M > S and M = batch (microbatch=1): deepest schedule, still exact
    rng = np.random.default_rng(2)
    d, batch = 8, 6
    per_stage = _make_stages(jax.random.key(3), 4, d)
    x = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))
    out = pipeline_apply(stack_stage_params(per_stage, mesh4), _mlp_stage, x,
                         mesh4, microbatch=1)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_sequential(per_stage, x)),
                               rtol=1e-5, atol=1e-6)


def test_pipeline_grad_matches_sequential(mesh4):
    rng = np.random.default_rng(3)
    d, batch = 8, 16
    per_stage = _make_stages(jax.random.key(4), 4, d)
    x = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))
    stacked = stack_stage_params(per_stage, mesh4)

    def pipe_loss(params):
        out = pipeline_apply(params, _mlp_stage, x, mesh4, microbatch=4)
        return jnp.mean((out - y) ** 2)

    def seq_loss(per_stage_list):
        out = x
        for p in per_stage_list:
            out = _mlp_stage(p, out)
        return jnp.mean((out - y) ** 2)

    g_pipe = jax.grad(pipe_loss)(stacked)
    g_seq = jax.grad(seq_loss)(per_stage)
    for s in range(4):
        np.testing.assert_allclose(np.asarray(g_pipe["w"][s]),
                                   np.asarray(g_seq[s]["w"]),
                                   rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(g_pipe["b"][s]),
                                   np.asarray(g_seq[s]["b"]),
                                   rtol=1e-4, atol=1e-6)


def test_pipeline_jit_train_step(mesh4):
    # one jitted SGD step through the pipeline drops the loss
    rng = np.random.default_rng(4)
    d, batch = 8, 16
    per_stage = _make_stages(jax.random.key(5), 4, d)
    x = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32) * 0.1)
    params = stack_stage_params(per_stage, mesh4)

    @jax.jit
    def step(params):
        def loss(p):
            out = pipeline_apply(p, _mlp_stage, x, mesh4, microbatch=4)
            return jnp.mean((out - y) ** 2)

        l, g = jax.value_and_grad(loss)(params)
        return jax.tree.map(lambda w, gw: w - 0.1 * gw, params, g), l

    p1, l0 = step(params)
    _, l1 = step(p1)
    assert float(l1) < float(l0)


def test_pipeline_validation(mesh4):
    per_stage = _make_stages(jax.random.key(6), 3, 8)  # wrong count
    with pytest.raises(ValueError, match="3 stage param sets"):
        stack_stage_params(per_stage, mesh4)
    with pytest.raises(ValueError, match="multiple of microbatch"):
        split_microbatches(jnp.zeros((10, 4)), 3)


def test_pipeline_tensor_parallel_stage_matches_sequential(mesh4):
    # pp x tp numerically: stage weights additionally sharded over "cols"
    # (column-split w0, row-split w1 — XLA's activation psum runs inside the
    # pipeline's Manual-rows context); output must still equal the plain
    # sequential composition
    from jax.sharding import NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(7)
    d, ff, batch = 8, 16, 16
    per_stage = [
        {"w0": jnp.asarray(rng.standard_normal((d, ff)).astype(np.float32)) / 3,
         "w1": jnp.asarray(rng.standard_normal((ff, d)).astype(np.float32)) / 4}
        for _ in range(4)
    ]
    x = jnp.asarray(rng.standard_normal((batch, d)).astype(np.float32))

    def fn(p, xb):
        return jnp.tanh(jax.nn.relu(xb @ p["w0"]) @ p["w1"])

    stacked = stack_stage_params(per_stage, mesh4)
    stacked = {
        "w0": jax.device_put(stacked["w0"],
                             NamedSharding(mesh4, P("rows", None, "cols"))),
        "w1": jax.device_put(stacked["w1"],
                             NamedSharding(mesh4, P("rows", "cols", None))),
    }
    out = jax.jit(lambda p, xx: pipeline_apply(p, fn, xx, mesh4,
                                               microbatch=4))(stacked, x)
    ref = x
    for p in per_stage:
        ref = fn(p, ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
