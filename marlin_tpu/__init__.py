"""marlin_tpu — a TPU-native distributed dense/sparse linear-algebra framework.

A ground-up rebuild of the capabilities of PasaLab/marlin (a Spark-based
distributed matrix library; see SURVEY.md) designed for TPU: matrices are
global ``jax.Array``s sharded over a ``jax.sharding.Mesh``, distributed
multiplies are SPMD programs whose collectives XLA schedules over ICI/DCN, and
per-block math runs on the MXU instead of netlib BLAS.

Quick start::

    import marlin_tpu as mt

    mesh = mt.create_mesh()                      # all local devices
    a = mt.DenseVecMatrix.random(0, 8000, 8000, mesh=mesh)
    b = mt.DenseVecMatrix.random(1, 8000, 8000, mesh=mesh)
    c = a.multiply(b)                            # adaptive: broadcast vs RMM
    (l, u, p) = a.lu_decompose(mode="dist")
"""

import sys as _sys
import time as _time

_T_IMPORT = _time.perf_counter()  # the `startup.import` span opens here
_JAX_PRELOADED = "jax" in _sys.modules

from .config import MarlinConfig, config_context, get_config, set_config  # noqa: F401
from .mesh import (  # noqa: F401
    COLS,
    ROWS,
    create_mesh,
    default_mesh,
    initialize_distributed,
    set_default_mesh,
)
from .matrix import (  # noqa: F401
    BlockMatrix,
    CoordinateMatrix,
    DenseMatrix,
    DenseVecMatrix,
    DistributedIntVector,
    DistributedMatrix,
    DistributedVector,
    OutOfCoreMatrix,
    SparseVecMatrix,
)
from .parallel import (  # noqa: F401
    ChunkPrefetcher,
    matmul,
    prefetch_chunks,
    ring_attention,
    ring_matmul,
    rmm_matmul,
    split_method,
    streamed_gramian,
    streamed_matmul,
    tune_multiply,
    ulysses_attention,
)
from .linalg import cholesky_decompose, compute_svd, inverse, lanczos, lu_decompose  # noqa: F401
from .io import (  # noqa: F401
    load_block_matrix_file,
    load_coordinate_matrix,
    load_matrix_file,
    load_svm_den_vec_matrix,
    save_matrix,
)
from .utils import evaluate, timer  # noqa: F401
from .lazy import fuse  # noqa: F401
from . import obs  # noqa: F401
from . import random  # noqa: F401

__version__ = "0.3.0"

obs.collectors.startup_record().add_span(
    "startup.import", _T_IMPORT, _time.perf_counter(),
    jax_preloaded=_JAX_PRELOADED)
