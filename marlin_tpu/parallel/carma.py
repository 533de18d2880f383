"""CARMA-inspired split selection.

The reference chooses how to split the (m, k, n) iteration space of a
distributed matmul by recursively halving the largest remaining dimension until
the core budget is spent (utils/MTUtils.scala:139-175, citing the CARMA paper
"Communication optimal parallel recursive rectangular matrix multiplication",
IPDPS'13; ``dimToSplit`` MTUtils.scala:204-213). Here the same heuristic picks
the shape of the 3-D device mesh used by :func:`marlin_tpu.parallel.rmm_matmul`
— i.e. it decides how many mesh slots each of m/k/n gets, which in turn decides
what crosses the ICI and WHEN:

- an m- or n-split moves OPERAND bytes. Of two row-sharded operands
  (``P("rows", None)``, a ``DenseVecMatrix``) an m-split leaves A where it
  lies, and an n-split fetches the rows of B's column panel that the chip does
  not hold. Those bytes exist before the dot starts, so they can travel under
  it: :func:`marlin_tpu.parallel.ring.ring_local` sends a panel of B along
  ``rows`` while the panel before it is multiplied, and a panel is hidden once
  a step's dot outlasts its transfer (on a v5e at ``precision="high"``: about
  2,800 rows of A a chip and more; smaller products are the broadcast
  strategy's).
- a k-split moves RESULT-sized partial products (a psum / reduce-scatter over
  ``k``), which do not exist until the dot has ended: that collective is
  exposed whatever the schedule.

So a tie between the sides goes to m, then n, and the contraction is split
only where it is strictly the longest remaining side (the reference's
``dimToSplit`` breaks the tie m, k, n: on Spark every split is a shuffle and
the order is immaterial).
"""

from __future__ import annotations


def dim_to_split(m: float, k: float, n: float) -> int:
    """Index (0=m, 1=k, 2=n) of the largest current per-shard dimension —
    the dimension whose split saves the most communication
    (MTUtils.scala:204-213). A tie goes to m, then n, then k: see the module
    docstring."""
    dims = (m, k, n)
    return max((0, 2, 1), key=lambda i: dims[i])


def split_method(m: int, k: int, n: int, parallelism: int) -> tuple[int, int, int]:
    """Choose (m_split, k_split, n_split) with product <= parallelism by
    repeatedly halving the largest per-shard dimension (MTUtils.scala:150-175).

    Unlike the reference (which creates m·k·n Spark tasks and can oversubscribe
    cores), the product here must not exceed the device count: each (i, j, l)
    cell is one device, not one task.
    """
    if parallelism < 1:
        raise ValueError("parallelism must be >= 1")
    ms = ks = ns = 1
    cur_m, cur_k, cur_n = float(m), float(k), float(n)
    while ms * ks * ns * 2 <= parallelism:
        i = dim_to_split(cur_m, cur_k, cur_n)
        if i == 0:
            if cur_m < 2:
                break
            ms, cur_m = ms * 2, cur_m / 2
        elif i == 1:
            if cur_k < 2:
                break
            ks, cur_k = ks * 2, cur_k / 2
        else:
            if cur_n < 2:
                break
            ns, cur_n = ns * 2, cur_n / 2
    return ms, ks, ns


def near_square_split(parallelism: int) -> int:
    """The reference's near-square special case: split = ⌊(3·cores)^(1/3)⌋ used
    when m≈k≈n (DenseVecMatrix.scala:208-213). Retained for API parity; the
    mesh-based path clamps it to the device budget."""
    s = int(round((3.0 * parallelism) ** (1.0 / 3.0)))
    return max(1, s)
