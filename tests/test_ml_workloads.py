"""Tests for the NN / LR / PageRank workloads (the reference exercises these
only via examples — SURVEY.md §4 lists them as untested)."""

import numpy as np
import pytest

import marlin_tpu as mt
from marlin_tpu.ml import (
    NeuralNetwork,
    build_transition_matrix,
    logistic_regression,
    pagerank,
)


@pytest.fixture()
def separable(mesh):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((256, 10)).astype(np.float32)
    w = rng.standard_normal(10)
    y = (x @ w > 0).astype(np.int64)
    return x, y


def test_nn_trains(mesh, separable):
    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=16, output_dim=2,
                       learning_rate=2.0, seed=0)
    params, losses = nn.train(data, y, iterations=200, batch_size=128)
    assert losses[-1] < losses[0] * 0.6
    assert nn.accuracy(params, data, y) > 0.9


def test_train_step_optax_sgd_direct(mesh, separable):
    """The facade routes optimizer='sgd' to the plain step, so exercise the
    optax 'sgd' branch through train_step_optax itself (ADVICE r2: this call
    used to fail with a message claiming 'sgd' is accepted)."""
    import jax
    import numpy as np

    from marlin_tpu.ml.neural_network import _build_tx, train_step_optax

    x, y = separable
    nn = NeuralNetwork(input_dim=10, hidden_dim=16, output_dim=2, seed=0)
    params = nn.init_params(mesh, np.float32)
    y1h = jax.nn.one_hot(y, 2, dtype=np.float32)
    opt_state = _build_tx("sgd", 0.5, 0.9).init(params)
    loss0 = None
    key = jax.random.key(0)
    for _ in range(20):
        key, sub = jax.random.split(key)
        params, opt_state, loss = train_step_optax(
            params, opt_state, jax.numpy.asarray(x), y1h, sub,
            batch_size=128, optimizer="sgd", lr=0.5)
        loss0 = loss0 if loss0 is not None else float(loss)
    assert float(loss) < loss0


@pytest.mark.parametrize("optimizer,lr",
                         [("sgd", 2.0), ("momentum", 0.5), ("adam", 0.01)])
def test_nn_optimizers(mesh, separable, optimizer, lr):
    # the optax-backed steps must train at least as reliably as plain SGD
    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=16, output_dim=2,
                       learning_rate=lr, seed=0, optimizer=optimizer)
    params, losses = nn.train(data, y, iterations=200, batch_size=128)
    assert losses[-1] < losses[0] * 0.6
    assert nn.accuracy(params, data, y) > 0.9


def test_nn_bad_optimizer(mesh, separable):
    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=16, output_dim=2,
                       optimizer="lbfgs")
    with pytest.raises(ValueError):
        nn.train(data, y, iterations=1, batch_size=32)


def test_nn_adam_checkpoint_resume(mesh, separable, tmp_path):
    # optimizer moments survive checkpoint/restore: resuming from the saved
    # {"params", "opt_state"} state must reproduce the uninterrupted run
    from marlin_tpu.io.checkpoint import load_checkpoint

    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=16, output_dim=2,
                       learning_rate=0.01, seed=0, optimizer="adam")
    full_params, _ = nn.train(data, y, iterations=8, batch_size=128)

    nn2 = NeuralNetwork(input_dim=10, hidden_dim=16, output_dim=2,
                        learning_rate=0.01, seed=0, optimizer="adam")
    p4, _ = nn2.train(data, y, iterations=4, batch_size=128,
                      checkpoint_dir=str(tmp_path), checkpoint_every=4)
    template = {"params": p4, "opt_state": nn2.last_opt_state}
    restored, step = load_checkpoint(template, str(tmp_path), step=4)
    assert step == 4
    # NOTE: the training key stream restarts from seed+1 on each train() call,
    # so an exact continuation needs the same batch draw — compare against a
    # fresh 4-iteration run from the restored state instead of bitwise parity
    p_resumed, losses = nn2.train(
        data, y, iterations=4, batch_size=128,
        params=restored["params"], opt_state=restored["opt_state"],
    )
    assert np.isfinite(losses[-1])
    # moments restored -> no loss spike: the resumed run must keep improving
    assert losses[-1] < losses[0] * 1.5
    for k in full_params:
        assert np.asarray(p_resumed[k]).shape == np.asarray(full_params[k]).shape


def test_nn_checkpoint_roundtrip(mesh, separable, tmp_path):
    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=8, output_dim=2, seed=1)
    params, _ = nn.train(data, y, iterations=10, batch_size=64,
                         checkpoint_dir=str(tmp_path), checkpoint_every=5)
    from marlin_tpu.io import load_checkpoint

    restored, step = load_checkpoint(params, str(tmp_path))
    assert step == 10
    for k in params:
        np.testing.assert_array_equal(np.asarray(params[k]), np.asarray(restored[k]))


def test_nn_one_hot_labels(mesh, separable):
    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=8, output_dim=2, seed=2)
    params, losses = nn.train(data, np.eye(2, dtype=np.float32)[y],
                              iterations=5, batch_size=64)
    assert np.isfinite(losses).all()


def test_lr_model(mesh, separable):
    x, y = separable
    rows = np.concatenate([y[:, None].astype(np.float32), x], axis=1)
    model = logistic_regression(mt.DenseVecMatrix.from_array(rows, mesh),
                                step_size=50.0, iterations=150)
    assert (model.predict(x) == y).mean() > 0.9
    # plain-array input accepted too
    model2 = logistic_regression(rows, step_size=50.0, iterations=50)
    assert model2.weights.shape == (11,)


def test_transition_matrix():
    m = build_transition_matrix([(0, 1), (0, 2), (1, 2)], n=3)
    np.testing.assert_allclose(m.sum(axis=0), np.ones(3), atol=1e-6)
    assert m[1, 0] == pytest.approx(0.5) and m[2, 0] == pytest.approx(0.5)
    # node 2 is dangling -> uniform column
    np.testing.assert_allclose(m[:, 2], np.full(3, 1 / 3), atol=1e-6)
    with pytest.raises(ValueError):
        build_transition_matrix([])


def test_pagerank_dense_vs_sparse(mesh):
    edges = [(1, 0), (2, 0), (3, 0), (0, 1), (2, 1), (3, 4), (4, 2)]
    m = build_transition_matrix(edges)
    r_dense = pagerank(mt.BlockMatrix.from_array(m, mesh), iterations=60)
    r_sparse = pagerank(mt.SparseVecMatrix.from_dense(m, mesh), iterations=60)
    assert r_dense.sum() == pytest.approx(1.0, abs=1e-5)
    np.testing.assert_allclose(r_dense, r_sparse, atol=1e-5)
    assert r_dense.argmax() == 0
    # stationarity: r ≈ damping*M@r + (1-d)/n
    resid = 0.85 * m @ r_dense + 0.15 / 5 - r_dense
    assert np.abs(resid).max() < 1e-4


def test_pagerank_edge_operator_matches_dense(mesh):
    from marlin_tpu.ml import build_transition_operator

    edges = [(1, 0), (2, 0), (3, 0), (0, 1), (2, 1), (3, 4), (4, 2)]
    r_dense = pagerank(build_transition_matrix(edges), iterations=60)
    # single-program edge form
    op = build_transition_operator(edges)
    r_edges = pagerank(op, iterations=60)
    np.testing.assert_allclose(r_edges, r_dense, atol=1e-5)
    # edge-sharded form over the whole mesh (7 edges pad to 8 devices)
    op_sh = build_transition_operator(edges, mesh=mesh)
    r_sharded = pagerank(op_sh, iterations=60)
    np.testing.assert_allclose(r_sharded, r_dense, atol=1e-5)
    assert op.nnz == 7 and op.shape == (5, 5)


def test_pagerank_edge_operator_graph_scale(mesh):
    # 100k nodes / 1M edges never densifies (dense would be 40 GB); the
    # full-scale criterion (10^7 nodes / 10^8 edges) has no cell yet
    rng = np.random.default_rng(0)
    n, e = 100_000, 1_000_000
    edges = np.stack([rng.integers(0, n, e), rng.integers(0, n, e)], axis=1)
    from marlin_tpu.ml import build_transition_operator

    op = build_transition_operator(edges, n=n, mesh=mesh)
    r = pagerank(op, iterations=5)
    assert r.shape == (n,)
    assert r.sum() == pytest.approx(1.0, abs=1e-4)
    assert (r >= 0).all()


def test_nn_deep(mesh, separable):
    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=(16, 12, 8), output_dim=2,
                       learning_rate=2.0, seed=0)
    assert nn.layer_sizes == (10, 16, 12, 8, 2)
    # deep sigmoid stacks train slowly (vanishing gradients) — the test is
    # about mechanics: 4 weight matrices, loss decreasing, better than chance
    params, losses = nn.train(data, y, iterations=400, batch_size=128)
    assert len(params) == 4  # w0..w3
    assert losses[-1] < losses[0]
    assert nn.accuracy(params, data, y) > 0.7


def test_nn_activation_validation(mesh, separable):
    x, y = separable
    data = mt.DenseVecMatrix.from_array(x, mesh)
    nn = NeuralNetwork(input_dim=10, hidden_dim=8, output_dim=2, activation="sigmod")
    with pytest.raises(ValueError):
        nn.train(data, y, iterations=1, batch_size=32)
    # relu + tanh both accepted
    for act in ("relu", "tanh"):
        nn = NeuralNetwork(input_dim=10, hidden_dim=8, output_dim=2,
                           learning_rate=0.2, activation=act, seed=1)
        params, losses = nn.train(data, y, iterations=20, batch_size=64)
        assert np.isfinite(losses).all()
