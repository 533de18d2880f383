"""BSR (block-sparse row) matrices: structured sparsity on the MXU.

The ELL path (ops/sparse_ell.py) is the right tool for *unstructured* sparsity
— its cost is one 1 KB B-row read per nonzero, which is HBM-gather-bound and
cannot ride the MXU. When sparsity is *structured* (block patterns from graph
communities, banded operators, pruned weight matrices), storing dense
bs×bs blocks changes the regime entirely: each stored block contributes a
(bs × bs) @ (bs × p) matmul, gathers move 64 KB panels instead of 1 KB rows,
and the MXU does the math. This is the TPU answer to the reference's
SparseMatrix CSC blocks (matrix/Matrices.scala:57-152), which are CPU
cache-blocked rather than systolic-array-shaped.

Storage: ``blocks`` (nnzb, bs, bs) dense block data, ``block_rows``/
``block_cols`` (nnzb,) indices into the (m/bs × n/bs) grid. SpMM gathers the
B panels by block column, runs one batched einsum, and segment-sums by block
row — chunked over nnzb with a fixed element budget like the ALS accumulator.

Backend verdict (measured, v5e, r5): ``backend="chunked"`` is the default and
the winner — 848 GFLOP/s vs 40 for the Pallas kernel at the bench config
(32768², block density 0.05, bs=128, p=256). Two kernel generations lost the
same way: the r2 input-index-map form serialized every panel copy behind
compute (Mosaic cannot look ahead through a data-dependent index map), and
the r3 manual double-buffered ``make_async_copy`` rewrite — although it
overlaps its own DMAs — still issues one ~64 KB panel DMA per stored block
from HBM while XLA's batched-gather formulation pipelines whole chunks
through wider reads. ``bsr_spmm_pallas`` stays importable as the documented
negative result; nothing routes to it by default.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import _interpret

__all__ = ["BsrMatrix", "bsr_from_dense", "bsr_from_coo", "bsr_spmm",
           "bsr_spmm_pallas"]


class BsrMatrix:
    def __init__(self, blocks, block_rows, block_cols, shape, block_size: int):
        # keep blocks sorted by block row: the SpMM scatter-reduce then runs
        # indices_are_sorted (an unsorted scatter-add is a TPU perf cliff) and
        # the Pallas path's in-VMEM output accumulation requires consecutive
        # same-row visits. The factories already emit sorted order; this
        # guards direct construction.
        # (skipped for tracers: a traced construction must come from an
        # already-sorted source. The host check reads only the (nnzb,) index
        # vector — trivial next to the block data itself.)
        if not isinstance(block_rows, jax.core.Tracer):
            br = np.asarray(block_rows)
            if br.size > 1 and np.any(br[1:] < br[:-1]):
                order = np.argsort(br, kind="stable")
                blocks = jnp.asarray(blocks)[order]
                block_rows = jnp.asarray(block_rows)[order]
                block_cols = jnp.asarray(block_cols)[order]
        self.blocks = blocks  # (nnzb, bs, bs)
        self.block_rows = block_rows  # (nnzb,) int32
        self.block_cols = block_cols  # (nnzb,) int32
        self.shape = tuple(int(s) for s in shape)
        self.block_size = int(block_size)

    @property
    def nnzb(self) -> int:
        return int(self.blocks.shape[0])

    @property
    def density(self) -> float:
        nbr = -(-self.shape[0] // self.block_size)
        nbc = -(-self.shape[1] // self.block_size)
        return self.nnzb / max(1, nbr * nbc)

    def to_dense(self) -> jax.Array:
        bs = self.block_size
        m, n = self.shape
        nbr, nbc = -(-m // bs), -(-n // bs)
        out = jnp.zeros((nbr, nbc, bs, bs), self.blocks.dtype)
        out = out.at[self.block_rows, self.block_cols].add(self.blocks)
        return out.transpose(0, 2, 1, 3).reshape(nbr * bs, nbc * bs)[:m, :n]

    def multiply(self, b, chunk_blocks: int | None = None,
                 backend: str = "chunked") -> jax.Array:
        """``backend="pallas"`` selects the scatter-free VMEM-accumulating
        kernel (:func:`bsr_spmm_pallas`); ``"chunked"`` the batched-einsum +
        sorted-segment-sum formulation; ``"auto"`` consults the autotune
        ranking over the generated family
        (:func:`~marlin_tpu.parallel.autotune.best_bsr_strategy` — timed
        once per configuration, winner persisted per device kind), so a
        hand-picked kernel can never shadow a faster formulation."""
        if backend == "auto":
            if chunk_blocks is not None:
                raise ValueError(
                    "chunk_blocks applies only to backend='chunked'")
            from ..parallel import autotune
            from .tile_family import parse_bsr_candidate

            cb = parse_bsr_candidate(autotune.best_bsr_strategy(self, b))
            if cb is None:
                return bsr_spmm_pallas(self, b)
            return bsr_spmm(self, b, cb)
        if backend == "pallas":
            if chunk_blocks is not None:
                raise ValueError(
                    "chunk_blocks applies only to backend='chunked'")
            return bsr_spmm_pallas(self, b)
        if backend != "chunked":
            raise ValueError(f"unknown BSR backend: {backend!r}")
        return bsr_spmm(self, b, chunk_blocks)

    def __repr__(self):
        return (f"BsrMatrix(shape={self.shape}, bs={self.block_size}, "
                f"nnzb={self.nnzb}, block_density={self.density:.4f})")


def bsr_from_dense(a, block_size: int = 128, tol: float = 0.0) -> BsrMatrix:
    """Extract the nonzero bs×bs blocks of a dense matrix (zero-padding ragged
    edges). Blocks whose max |entry| <= tol are dropped."""
    a = np.asarray(a)
    m, n = a.shape
    bs = block_size
    mp, np_ = -(-m // bs) * bs, -(-n // bs) * bs
    if (mp, np_) != (m, n):
        a = np.pad(a, ((0, mp - m), (0, np_ - n)))
    grid = a.reshape(mp // bs, bs, np_ // bs, bs).transpose(0, 2, 1, 3)
    mags = np.abs(grid).max(axis=(2, 3))
    bi, bj = np.nonzero(mags > tol)
    blocks = grid[bi, bj]
    return BsrMatrix(
        jnp.asarray(blocks), jnp.asarray(bi, jnp.int32), jnp.asarray(bj, jnp.int32),
        (m, n), bs,
    )


def bsr_from_coo(rows, cols, vals, shape, block_size: int = 128) -> BsrMatrix:
    """Build BSR directly from COO triplets without ever densifying —
    memory is O(nnzb · bs²) (the BSR itself), so huge sparse matrices whose
    nonzeros cluster into blocks convert at block-storage cost."""
    rows = np.asarray(rows, np.int64)
    cols = np.asarray(cols, np.int64)
    vals = np.asarray(vals)
    m, n = shape
    bs = block_size
    if vals.size == 0:
        return BsrMatrix(
            jnp.zeros((0, bs, bs), vals.dtype if vals.dtype != np.int64 else np.float32),
            jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32), (m, n), bs,
        )
    nbc = -(-n // bs)
    block_id = (rows // bs) * nbc + (cols // bs)
    uniq, inv = np.unique(block_id, return_inverse=True)
    # sort + reduceat: vectorized accumulation in the values' own dtype with
    # O(nnz) extra memory (np.add.at is per-element slow; np.bincount would
    # force a float64 intermediate the size of all blocks)
    flat = inv * (bs * bs) + (rows % bs) * bs + (cols % bs)
    order = np.argsort(flat, kind="stable")
    fs, vs = flat[order], vals[order]
    starts = np.flatnonzero(np.r_[True, fs[1:] != fs[:-1]])
    sums = np.add.reduceat(vs, starts)
    blocks = np.zeros(len(uniq) * bs * bs, vals.dtype)
    blocks[fs[starts]] = sums
    blocks = blocks.reshape(len(uniq), bs, bs)
    return BsrMatrix(
        jnp.asarray(blocks),
        jnp.asarray(uniq // nbc, jnp.int32),
        jnp.asarray(uniq % nbc, jnp.int32),
        (m, n), bs,
    )


@functools.partial(
    jax.jit, static_argnames=("n_block_rows", "chunk", "accum_dtype")
)
def _bsr_spmm_chunked(blocks, brows, bcols, b_panels, n_block_rows: int,
                      chunk: int, accum_dtype=jnp.float32):
    nnzb = blocks.shape[0]
    n_chunks = nnzb // chunk  # pre-padded by caller
    bs, p = b_panels.shape[1], b_panels.shape[2]

    def body(carry, idx):
        out = carry
        blk = blocks[idx]                       # (chunk, bs, bs)
        panels = b_panels[bcols[idx]]           # (chunk, bs, p) gather
        prod = jnp.einsum("abc,acd->abd", blk, panels,
                          preferred_element_type=accum_dtype)
        # +1 spill row swallows padding entries routed to row n_block_rows;
        # rows are sorted (constructor invariant), which matters on TPU
        out = out + jax.ops.segment_sum(prod, brows[idx], n_block_rows + 1,
                                        indices_are_sorted=True)
        return out, None

    out0 = jnp.zeros((n_block_rows + 1, bs, p), accum_dtype)
    idxs = jnp.arange(n_chunks * chunk).reshape(n_chunks, chunk)
    out, _ = jax.lax.scan(body, out0, idxs)
    return out[:n_block_rows]


def _bsr_pallas_kernel(brows, bcols, copy_of, slot_of, blk_ref, b_hbm, o_ref,
                       b_buf, sem):
    """Per stored block: one (bs×bs)@(bs×pp) MXU matmul into the resident
    output tile, with the B panel double-buffered by hand.

    The first formulation of this kernel selected the B panel with a
    scalar-prefetched *input index map* (``lambda j, br, bc: (bc[j], 0, 0)``).
    Mosaic cannot look ahead through a data-dependent map, so every panel
    copy serialized against the previous step's compute — measured 10-30×
    slower than the chunked XLA path (40-54 GFLOP/s; ROADMAP round-2 note).
    Here the panel lives in HBM (``pl.ANY``) and the kernel itself starts the
    DMA for step j+1's panel before waiting on step j's: the copy engine runs
    ahead of the MXU again, which is exactly what Mosaic's automatic
    pipelining would have done had the index been static."""
    j = pl.program_id(0)
    nnzb = pl.num_programs(0)
    # consecutive blocks sharing a column reuse the resident panel: slot_of[j]
    # is the parity of distinct-panel copies up to j (precomputed host-side),
    # copy_of[j] == 0 marks "same column as j-1, no DMA". This keeps the
    # skip-copy behavior Mosaic's index-map pipelining would have given.
    slot = slot_of[j]

    def panel_dma(s, idx):
        return pltpu.make_async_copy(b_hbm.at[bcols[idx]], b_buf.at[s],
                                     sem.at[s])

    @pl.when(j == 0)
    def _warmup():
        panel_dma(0, 0).start()

    @pl.when((j + 1 < nnzb) & (copy_of[jnp.minimum(j + 1, nnzb - 1)] == 1))
    def _prefetch_next():
        # the slot being overwritten held the panel last read two copies ago;
        # its final reader was an earlier (sequential) grid step
        panel_dma(slot_of[jnp.minimum(j + 1, nnzb - 1)], j + 1).start()

    # output block index is brows[j] (scalar-prefetch-driven index map): while
    # consecutive programs hit the same block row, the output tile stays
    # resident in VMEM and accumulates — no scatter anywhere. Initialize on
    # the first visit of each row (rows are sorted, constructor invariant).
    first = jnp.where(j == 0, True, brows[j] != brows[jnp.maximum(j - 1, 0)])

    @pl.when(first)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(copy_of[j] == 1)
    def _await_panel():
        panel_dma(slot, j).wait()

    o_ref[:] += jnp.dot(
        blk_ref[0], b_buf[slot], preferred_element_type=jnp.float32
    )[None]


def bsr_spmm_pallas(bsr: BsrMatrix, b, interpret: bool | None = None) -> jax.Array:
    """``bsr @ b`` as one Pallas pass: grid over stored blocks, output tiles
    selected by scalar-prefetched block-row indices and accumulated in VMEM,
    B panels double-buffered into VMEM by explicit ``make_async_copy`` (see
    :func:`_bsr_pallas_kernel` for why manual DMA). Versus :func:`bsr_spmm`
    this removes the block-row scatter-reduce and the (chunk, bs, p) gather
    materialization entirely."""
    b = jnp.asarray(b.logical() if hasattr(b, "logical") else b)
    m, n = bsr.shape
    if b.shape[0] != n:
        raise ValueError(f"inner dim mismatch: {bsr.shape} @ {b.shape}")
    bs = bsr.block_size
    p = b.shape[1]
    out_dtype = jnp.promote_types(bsr.blocks.dtype, b.dtype)
    if jnp.promote_types(out_dtype, jnp.float32) != jnp.dtype(jnp.float32):
        # the kernel computes in f32 (Mosaic has no f64 MXU path); wider
        # operands route to the chunked formulation, which accumulates in the
        # promoted dtype — same numerics contract as the ELL/BCOO paths
        return bsr_spmm(bsr, b)
    if bsr.nnzb == 0:
        return jnp.zeros((m, p), out_dtype)
    if interpret is None:
        interpret = _interpret()
    np_ = -(-n // bs) * bs
    pp = -(-p // 128) * 128 if not interpret else p
    if (np_, pp) != (n, p):
        b = jnp.pad(b, ((0, np_ - n), (0, pp - p)))
    b_panels = b.reshape(np_ // bs, bs, pp)
    n_block_rows = -(-m // bs)

    brows = jnp.asarray(bsr.block_rows, jnp.int32)
    bcols = jnp.asarray(bsr.block_cols, jnp.int32)
    blocks = bsr.blocks
    nnzb = bsr.nnzb
    f32 = jnp.float32
    # copy_of[j]=1 where step j needs a fresh panel DMA (column differs from
    # j-1); slot_of[j] = parity of copies so far = the double-buffer slot
    # holding step j's panel. O(nnzb) int32 work, scalar-prefetched.
    copy_of = jnp.concatenate(
        [jnp.ones((1,), jnp.int32),
         (bcols[1:] != bcols[:-1]).astype(jnp.int32)])
    slot_of = (jnp.cumsum(copy_of) - 1) % 2
    out = pl.pallas_call(
        _bsr_pallas_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(nnzb,),
            in_specs=[
                pl.BlockSpec((1, bs, bs), lambda j, *_: (j, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),  # panels stay in HBM
            ],
            out_specs=pl.BlockSpec((1, bs, pp),
                                   lambda j, br, *_: (br[j], 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bs, pp), f32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_block_rows, bs, pp), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(brows, bcols, copy_of, slot_of.astype(jnp.int32),
      blocks.astype(f32), b_panels.astype(f32))
    # block rows with no stored blocks are never visited -> undefined; mask
    has_blocks = jnp.zeros((n_block_rows,), bool).at[brows].set(
        True, indices_are_sorted=True)
    out = jnp.where(has_blocks[:, None, None], out, 0.0)
    return out.reshape(n_block_rows * bs, pp)[:m, :p].astype(out_dtype)


def bsr_spmm(bsr: BsrMatrix, b, chunk_blocks: int | None = None) -> jax.Array:
    """``bsr @ b`` with dense result, batched block matmuls on the MXU."""
    b = jnp.asarray(b.logical() if hasattr(b, "logical") else b)
    m, n = bsr.shape
    if b.shape[0] != n:
        raise ValueError(f"inner dim mismatch: {bsr.shape} @ {b.shape}")
    bs = bsr.block_size
    p = b.shape[1]
    if bsr.nnzb == 0:
        return jnp.zeros((m, p), b.dtype)
    np_ = -(-n // bs) * bs
    if np_ != n:
        b = jnp.pad(b, ((0, np_ - n), (0, 0)))
    b_panels = b.reshape(np_ // bs, bs, p)
    n_block_rows = -(-m // bs)

    if chunk_blocks is None:
        # bound the (chunk, bs, p) gather + product buffers to ~32 MB
        chunk_blocks = max(1, (1 << 23) // (bs * max(p, bs)))
    nnzb = bsr.nnzb
    chunk_blocks = max(1, min(chunk_blocks, nnzb))
    pad = (-nnzb) % chunk_blocks
    blocks, brows, bcols = bsr.blocks, bsr.block_rows, bsr.block_cols
    if pad:
        blocks = jnp.pad(blocks, ((0, pad), (0, 0), (0, 0)))
        # padding blocks are zero; route them to the spill row anyway
        brows = jnp.pad(brows, (0, pad), constant_values=n_block_rows)
        bcols = jnp.pad(bcols, (0, pad))
    # accumulate in at least f32, wider when either operand is (advisor
    # finding: the hard-coded f32 accumulator silently narrowed f64 inputs
    # relative to the ELL/BCOO paths behind the same multiply(format=...) switch)
    accum = jnp.promote_types(jnp.promote_types(blocks.dtype, b.dtype),
                              jnp.float32)
    out = _bsr_spmm_chunked(blocks, brows, bcols, b_panels, n_block_rows,
                            chunk_blocks, accum)
    # result dtype = natural promotion of the operands, matching the ELL/BCOO
    # paths (f32 in, f32 out; any f64 operand keeps the result f64)
    out_dtype = jnp.promote_types(blocks.dtype, b.dtype)
    return out.reshape(n_block_rows * bs, p)[:m].astype(out_dtype)
