"""Incremental PageRank queries as a BucketProgram.

The graph stays resident in edge form (:class:`~marlin_tpu.ml.pagerank
.TransitionOperator` — the never-densify representation) next to a live
rank vector. A request names a node (payload ``{"node": int, "k": int?}``)
and gets the top-k *out-neighbors of that node by current global rank* —
the "who should this page link-surf to" query — computed as one batched
edge-mask + ``lax.top_k`` over the resident arrays.

"Incremental" is :meth:`PageRankQueryProgram.refresh`: between queries the
operator advances the resident rank vector by a few power-iteration steps
(:func:`~marlin_tpu.ml.pagerank._pagerank_step`, the same edge-form SpMV
the offline solver runs), so ranks track the graph without ever blocking
the serving path — queries read whatever vector is installed, swaps are
atomic under the program lock, and refresh compiles once per iteration
count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...config import get_config
from ...ml.pagerank import (TransitionOperator, _pagerank_step,
                            build_transition_operator)
from . import register_program
from .base import BucketProgram

__all__ = ["PageRankQueryProgram"]


@functools.partial(jax.jit, static_argnames=("k",))
def _pr_neighbor_topk(src, dst, ranks, nodes, k: int):
    """Top-k out-neighbors by rank for a padded batch of query nodes: mask
    the edge list per query row, score each edge by its destination's rank,
    top-k over the edge axis. (W, E) is the honest cost of an unsorted
    adjacency — the admission budget charges exactly this row."""
    sel = src[None, :] == nodes[:, None]                      # (W, E)
    scored = jnp.where(sel, ranks[dst][None, :], -jnp.inf)    # (W, E)
    vals, eidx = jax.lax.top_k(scored, k)
    return vals, dst[eidx]


@functools.partial(jax.jit, static_argnames=("n", "iterations"))
def _pr_refresh(r, src, dst, inv_deg, dangling, damping, n: int,
                iterations: int):
    def body(_, rr):
        return _pagerank_step(rr, src, dst, None, inv_deg, dangling,
                              damping, n)
    return jax.lax.fori_loop(0, iterations, body, r)


@register_program
class PageRankQueryProgram(BucketProgram):
    """node → top-k out-neighbors by live PageRank over a resident graph."""

    name = "pagerank"
    resource_unit = "one padded edge-mask row: num_edges x 4 bytes"

    def __init__(self, edges, n: int | None = None, damping: float = 0.85):
        super().__init__()
        op = (edges if isinstance(edges, TransitionOperator)
              else build_transition_operator(edges, n))
        if op.mesh is not None or op.weight is not None:
            raise ValueError("serving wants an unsharded operator "
                             "(build without mesh=)")
        self._op = op
        self.n = int(op.n)
        self.num_edges = int(op.nnz)
        self._damping = jnp.asarray(damping, jnp.float32)
        self._ranks = jnp.full((self.n,), 1.0 / self.n, jnp.float32)
        cfg = get_config()
        ks = tuple(sorted({int(k) for k in cfg.serve_program_topk
                           if int(k) <= self.num_edges}))
        if not ks:
            raise ValueError(
                f"no serve_program_topk value fits num_edges="
                f"{self.num_edges} (got {cfg.serve_program_topk!r})")
        self._ks = ks
        self.refresh_count = 0
        self._ledger_register(op.src, op.dst, op.inv_deg, op.dangling,
                              self._ranks)

    def refresh(self, iterations: int = 1) -> np.ndarray:
        """Advance the resident rank vector ``iterations`` power steps and
        install it atomically; returns the new ranks (host copy). One
        compile per distinct ``iterations`` value — callers should pick
        one cadence and stick to it."""
        op = self._op
        with self._lock:
            r = self._ranks
        r = _pr_refresh(r, op.src, op.dst, op.inv_deg, op.dangling,
                        self._damping, self.n, int(iterations))
        with self._lock:
            self._ranks = r
            self.refresh_count += 1
        return np.asarray(jax.device_get(r))

    def ranks(self) -> np.ndarray:
        with self._lock:
            return np.asarray(jax.device_get(self._ranks))

    # ---------------------------------------------------------------- policy
    def buckets(self):
        return [(k,) for k in self._ks]

    def validate(self, request):
        p = request.payload
        if not isinstance(p, dict) or "node" not in p:
            return (f"program {self.name!r} needs payload "
                    f"{{'node': int, 'k': int?}}, got {type(p).__name__}")
        node = p["node"]
        if not 0 <= int(node) < self.n:
            return f"node {node} out of range [0, {self.n})"
        k = int(p.get("k", self._ks[0]))
        if k < 1:
            return f"k must be >= 1, got {k}"
        return None

    def pick_bucket(self, request):
        k = int(request.payload.get("k", self._ks[0]))
        for kb in self._ks:
            if kb >= k:
                return (kb,)
        return None

    def refuse_no_bucket(self, request):
        return (f"no bucket fits program='pagerank' k="
                f"{request.payload.get('k')} (k buckets {list(self._ks)})")

    def admission_cost(self, request, bucket):
        return self.num_edges * 4

    # ------------------------------------------------------------- mechanism
    def warmup(self) -> int:
        n = 0
        op = self._op
        nodes = {w: jnp.zeros((w,), jnp.int32) for w in self.widths}
        with self._lock:
            ranks = self._ranks
        for (k,) in self.buckets():
            for w in self.widths:
                _pr_neighbor_topk(op.src, op.dst, ranks, nodes[w], k=k)
                n += 1
        return n

    def step(self, bucket, requests):
        (k,) = bucket
        op = self._op
        w = self.step_width(len(requests))
        nodes = np.full((w,), -1, np.int32)  # -1 matches no src: empty rows
        for i, r in enumerate(requests):
            # analyze: ignore[host-sync] — payload ints are host data
            nodes[i] = int(r.payload["node"])
        with self._lock:
            ranks = self._ranks
        vals, items = _pr_neighbor_topk(op.src, op.dst, ranks,
                                        jnp.asarray(nodes), k=k)
        # analyze: ignore[host-sync] — THE one intentional sync per program
        # step: the one-shot batch retires here with host Result values
        vals = np.asarray(jax.device_get(vals))
        # analyze: ignore[host-sync] — same fetch, second output
        items = np.asarray(jax.device_get(items))
        out = []
        for i, r in enumerate(requests):
            want = int(r.payload.get("k", k))
            good = np.isfinite(vals[i, :want])  # < k out-neighbors pad -inf
            out.append({"items": items[i, :want][good].copy(),
                        "scores": vals[i, :want][good].copy()})
        return out
