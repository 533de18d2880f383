"""Cross-strategy equivalence sweep: every multiply engine must agree with
every other on the same inputs — the invariant behind the adaptive dispatch
(the reference only ever compares one RMM variant at a time; here agreement is
enforced as a property over shapes, layouts, and precisions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import marlin_tpu as mt
from marlin_tpu.parallel.matmul import plan_padded

SHAPES = [(16, 16, 16), (33, 17, 9), (8, 64, 8), (50, 3, 41)]


@pytest.mark.parametrize("mkn", SHAPES)
def test_all_strategies_agree(mesh, mkn):
    m, k, n = mkn
    rng = np.random.default_rng(sum(mkn))
    a = rng.standard_normal((m, k)).astype(np.float32)
    b = rng.standard_normal((k, n)).astype(np.float32)
    ma = mt.BlockMatrix.from_array(a, mesh)
    mb = mt.BlockMatrix.from_array(b, mesh)
    oracle = a @ b
    results = {
        s: ma.multiply(mb, strategy=s).to_numpy()
        for s in ("broadcast", "rmm", "gspmd", "ring")
    }
    for name, out in results.items():
        np.testing.assert_allclose(out, oracle, rtol=1e-3, atol=1e-3, err_msg=name)
    # pairwise: engines may reassociate f32 sums differently, but at these
    # contraction depths they must stay within a few ulps of each other
    base = results["broadcast"]
    for name, out in results.items():
        np.testing.assert_allclose(out, base, rtol=5e-4, atol=5e-4,
                                   err_msg=f"broadcast vs {name}")


def test_precision_passthrough(mesh):
    rng = np.random.default_rng(0)
    a = rng.standard_normal((64, 512)).astype(np.float32)
    b = rng.standard_normal((512, 64)).astype(np.float32)
    ma = mt.BlockMatrix.from_array(a, mesh)
    mb = mt.BlockMatrix.from_array(b, mesh)
    # the precision kwarg must be accepted by every engine and keep results at
    # f32-accumulation accuracy vs the f64 oracle (~4e-5 measured at k=512;
    # 2e-4 bound leaves headroom for reassociation). NOTE: on the CPU test
    # mesh all precisions compute in f32, so a *dropped* precision kwarg is
    # only detectable on TPU — the on-chip benches cover that half.
    oracle = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(oracle).max()
    for s in ("broadcast", "rmm", "gspmd", "ring"):
        out = ma.multiply(mb, strategy=s, precision="highest").to_numpy()
        assert np.abs(out - oracle).max() / scale < 2e-4, s


@pytest.mark.parametrize("klass", ["DenseVecMatrix", "BlockMatrix"])
def test_svd_layout_invariance(mesh, klass):
    rng = np.random.default_rng(1)
    a = rng.standard_normal((40, 12)).astype(np.float32)
    m = getattr(mt, klass).from_array(a, mesh)
    res = m.compute_svd(3, mode="local-eigs")
    np.testing.assert_allclose(res.s, np.linalg.svd(a, compute_uv=False)[:3],
                               rtol=2e-2)


def test_block_format_uneven_grid(tmp_path, mesh):
    # block save/load with shapes that don't divide the mesh
    rng = np.random.default_rng(2)
    a = rng.standard_normal((11, 7)).astype(np.float32)
    m = mt.BlockMatrix.from_array(a, mesh)
    p = str(tmp_path / "blk.txt")
    m.save_to_file_system(p, fmt="block")
    back = mt.load_block_matrix_file(p, mesh)
    np.testing.assert_allclose(back.to_numpy(), a, rtol=1e-6, atol=1e-6)


def test_chained_mixed_strategies(mesh):
    # (A @ B) via ring, then @ C via rmm, then elementwise — results compose
    rng = np.random.default_rng(3)
    a = rng.standard_normal((24, 18)).astype(np.float32)
    b = rng.standard_normal((18, 30)).astype(np.float32)
    c = rng.standard_normal((30, 10)).astype(np.float32)
    ma = mt.DenseVecMatrix.from_array(a, mesh)
    ab = ma.multiply(mt.DenseVecMatrix.from_array(b, mesh), strategy="ring")
    abc = ab.multiply(mt.BlockMatrix.from_array(c, mesh), strategy="rmm")
    final = abc.add(1.0).multiply(0.5)
    np.testing.assert_allclose(final.to_numpy(), (a @ b @ c + 1.0) * 0.5,
                               rtol=1e-3, atol=1e-3)


# ---- the ring over B's column panels (program ``ring2d``): the (rows, 1,
# cols) split of two row-sharded operands on the caller's whole mesh

_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
                "collective-permute")


def _operands(mkn, seed=0):
    m, k, n = mkn
    rng = np.random.default_rng(seed + sum(mkn))
    return (rng.standard_normal((m, k)).astype(np.float32),
            rng.standard_normal((k, n)).astype(np.float32))


def _plan_of(ma, mb, **kw):
    """The plan ``ma.multiply(mb, **kw)`` dispatches, and its compiled HLO."""
    mesh = ma.mesh
    cols = mesh.shape["cols"] > 1
    out = NamedSharding(mesh, P("rows", "cols") if cols else P("rows", None))
    (m, k), n = ma.shape, mb.shape[1]
    pad = (-(-m // mesh.shape["rows"]) * mesh.shape["rows"],
           -(-n // mesh.shape["cols"]) * mesh.shape["cols"] if cols else n)
    plan = plan_padded(ma.data, mb.data, (m, k, n), out, pad, **kw)
    hlo = plan.fn.lower(ma.data, mb.data).compile().as_text()
    return plan, {c for c in _COLLECTIVES if c in hlo}


def _dot32(a, b):
    return np.asarray(jnp.dot(a, b, precision="highest",
                              preferred_element_type=jnp.float32))


@pytest.mark.parametrize("mkn", [(64, 64, 64), (50, 70, 90), (37, 41, 53)],
                         ids=["divides", "m<k<n-padded", "odd-padded"])
@pytest.mark.parametrize("shape", [(2, 2), (4, 2), (2, 4), (8, 1), (1, 8)],
                         ids=lambda s: f"{s[0]}x{s[1]}")
def test_ring2d_matches_dot_on_every_mesh(shape, mkn):
    """The m x n split as a ring along ``rows`` against ``jnp.dot`` in
    float32, on shapes that do and do not divide the mesh and m != k != n;
    the plan counts (rows - 1) panels of B as what a chip is sent."""
    mesh = mt.create_mesh(shape)
    a, b = _operands(mkn)
    ma = mt.DenseVecMatrix.from_array(a, mesh)
    mb = mt.DenseVecMatrix.from_array(b, mesh)
    kw = dict(strategy="rmm", split=(shape[0], 1, shape[1]),
              precision="highest")
    plan, collectives = _plan_of(ma, mb, **kw)
    assert plan.program == "ring2d"
    assert collectives <= {"collective-permute"}
    pr, pc = shape
    panel = -(-mkn[1] // pr) * -(-mkn[2] // pc) * 4
    assert plan.moved_bytes == (pr - 1) * panel
    c = ma.multiply(mb, **kw)
    assert c.shape == (mkn[0], mkn[2])
    np.testing.assert_allclose(c.to_numpy(), _dot32(a, b), rtol=1e-5, atol=1e-5)
    # the pads of the result stay zero (the zero-pad invariant)
    np.testing.assert_allclose(float(c.sum()), c.to_numpy().sum(), rtol=1e-4,
                               atol=1e-3)


def test_auto_takes_the_ring_on_a_cube_and_says_so(monkeypatch):
    """``strategy="auto"`` on a cube over a full 2x2 mesh: CARMA's (2, 1, 2),
    the ring program, one ``marlin:matmul.dispatch`` span a product with the
    plan's fields; the plan's first product in the process has a
    ``matmul.first_dispatch`` span inside it, a later one has not."""
    from marlin_tpu.parallel.matmul import _plan
    from marlin_tpu.utils import tracing

    _plan.cache_clear()  # whatever ran before: this plan is new
    spans = []
    real = tracing.annotate
    monkeypatch.setattr(tracing, "annotate", lambda name, **f: (
        spans.append((name, f)), real(name, **f))[1])
    mesh = mt.create_mesh((2, 2))
    a, b = _operands((96, 96, 96))
    ma = mt.DenseVecMatrix.from_array(a, mesh)
    mb = mt.DenseVecMatrix.from_array(b, mesh)
    c = ma.multiply(mb, broadcast_threshold_mb=0, precision="highest")
    np.testing.assert_allclose(c.to_numpy(), _dot32(a, b), rtol=1e-5, atol=1e-5)
    dispatch = ("matmul.dispatch", dict(
        strategy="rmm", split="2x1x2", program="ring2d",
        moved_bytes=48 * 48 * 4))
    assert spans == [dispatch, ("matmul.first_dispatch", dict(
        program="ring2d", split="2x1x2"))]
    del spans[:]
    ma.multiply(mb, broadcast_threshold_mb=0, precision="highest")
    assert spans == [dispatch]
    _, collectives = _plan_of(ma, mb, broadcast_threshold_mb=0)
    assert collectives == {"collective-permute"}


@pytest.mark.parametrize("case", ["split-2x2x1", "block-a", "block-b",
                                  "host-b", "subset-split"])
def test_every_other_layout_keeps_its_program(case):
    """An explicit k-split, a ``BlockMatrix`` operand and an operand from the
    host all run the 3-D mesh program; a split over a subset of the mesh has
    no fused program and takes the logical-array path, as before."""
    mesh = mt.create_mesh((2, 2))
    a, b = _operands((64, 64, 64), seed=1)
    ma = mt.DenseVecMatrix.from_array(a, mesh)
    mb = mt.DenseVecMatrix.from_array(b, mesh)
    kw = dict(strategy="rmm", precision="highest")
    if case == "split-2x2x1":
        kw["split"] = (2, 2, 1)
    elif case == "block-a":
        ma = mt.BlockMatrix.from_array(a, mesh)
    elif case == "block-b":
        mb = mt.BlockMatrix.from_array(b, mesh)
    elif case == "subset-split":
        kw["split"] = (2, 1, 1)
    out = NamedSharding(mesh, P("rows", "cols"))
    if case in ("host-b", "subset-split"):
        other = b if case == "host-b" else mb
        plan = plan_padded(ma.data, jnp.asarray(b) if case == "host-b"
                           else mb.data, (64, 64, 64), out, (64, 64), **kw)
    else:
        other = mb
        plan, collectives = _plan_of(ma, mb, **kw)
    if case == "subset-split":
        assert plan is None
    else:
        assert plan.program == "rmm"
    if case == "split-2x2x1":
        assert collectives & {"all-reduce", "reduce-scatter"}
        # B's half that the chip lacks, and half of its partial block
        assert plan.moved_bytes == 32 * 64 * 4 + 32 * 64 * 4 // 2
    np.testing.assert_allclose(ma.multiply(other, **kw).to_numpy(),
                               _dot32(a, b), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 1), (2, 4)], ids=["8x1", "2x4"])
def test_strategy_ring_runs_the_shared_body(shape):
    """``strategy="ring"`` is the same body over one axis: p dots, p - 1
    transfers (the last step sends nothing), C row-sharded."""
    from marlin_tpu.parallel import ring

    mesh = mt.create_mesh(shape)
    a, b = _operands((40, 24, 56), seed=2)
    ma = mt.DenseVecMatrix.from_array(a, mesh)
    c = ma.multiply(mt.DenseVecMatrix.from_array(b, mesh), strategy="ring",
                    precision="highest")
    np.testing.assert_allclose(c.to_numpy(), _dot32(a, b), rtol=1e-5, atol=1e-5)
    row = NamedSharding(mesh, P("rows", None))
    x = jax.ShapeDtypeStruct((40, 24), jnp.float32, sharding=row)
    y = jax.ShapeDtypeStruct((24, 56), jnp.float32, sharding=row)
    jaxpr = str(jax.make_jaxpr(ring._ring_fn(mesh, "rows", "highest",
                                             jnp.float32))(x, y))
    assert jaxpr.count("ppermute") == shape[0] - 1
    assert jaxpr.count("dot_general") == shape[0]


@pytest.mark.parametrize("split", [None, (2, 2, 1)], ids=["auto", "k-split"])
def test_traced_operands_show_no_layout_and_still_multiply(split):
    """Under a caller's ``jit`` an operand shows no sharding: the plan takes
    it as row-sharded (the ring's own constraint then asks for that), at
    shapes that divide nothing, and an explicit k-split keeps its program."""
    from marlin_tpu.parallel.matmul import matmul_padded

    mesh = mt.create_mesh((2, 2))
    out = NamedSharding(mesh, P("rows", "cols"))
    a, b = _operands((7, 5, 6), seed=3)
    seen = []

    def f(x, y):
        seen.append(plan_padded(x, y, (7, 5, 6), out, (8, 6), strategy="rmm",
                                split=split).program)
        return matmul_padded(x, y, (7, 5, 6), out, (8, 6), strategy="rmm",
                             split=split, precision="highest")

    c = np.asarray(jax.jit(f)(a, b))
    assert seen == ["ring2d" if split is None else "rmm"]
    np.testing.assert_allclose(c[:7, :6], _dot32(a, b), rtol=1e-5, atol=1e-5)
    assert not c[7:].any()
