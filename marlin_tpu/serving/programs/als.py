"""ALS recommendation scoring as a BucketProgram.

The paper's flagship workload (PAPER.md §0) served online: factor matrices
trained by :mod:`marlin_tpu.ml.als` stay device-resident, a request names a
user (payload ``{"user": int, "k": int?}``) and gets that user's top-k items
by inner-product score — one gather, one (W, items) matmul, one
``lax.top_k``, batched over a padded width. Buckets are the configured k
values (``serve_program_topk``); a requested k rounds up to the smallest
bucket and the Result slices back down, exactly like LM steps round up to a
decode bucket.

:meth:`ALSScoreProgram.swap_model` installs freshly trained factors
atomically under the program lock — same shapes hit the same compiled
programs (factors are traced operands), so a hot factor update never
recompiles and never tears a batch (the worker reads both matrices under
the same lock acquisition).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...config import get_config
from . import register_program
from .base import BucketProgram

__all__ = ["ALSScoreProgram"]


@functools.partial(jax.jit, static_argnames=("k",))
def _als_topk(user_factors, item_factors, users, k: int):
    """Top-k items for a padded batch of users: scores = U[users] @ Vᵀ."""
    u = jnp.take(user_factors, users, axis=0)        # (W, rank)
    scores = u @ item_factors.T                      # (W, items)
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx


def _factors(model):
    """Device arrays from an ALSModel (or any .user_features/
    .product_features pair, or a raw (users, items) array 2-tuple)."""
    uf = getattr(model, "user_features", None)
    pf = getattr(model, "product_features", None)
    if uf is None or pf is None:
        uf, pf = model
    if hasattr(uf, "logical"):
        uf = uf.logical()
    if hasattr(pf, "logical"):
        pf = pf.logical()
    uf = jnp.asarray(uf, jnp.float32)
    pf = jnp.asarray(pf, jnp.float32)
    if uf.ndim != 2 or pf.ndim != 2 or uf.shape[1] != pf.shape[1]:
        raise ValueError(
            f"factor shapes disagree: users {uf.shape}, items {pf.shape}")
    return uf, pf


@register_program
class ALSScoreProgram(BucketProgram):
    """user → top-k item recommendations against resident ALS factors."""

    name = "als"
    resource_unit = "one padded score row: num_items x 4 bytes"

    def __init__(self, model):
        super().__init__()
        self._uf, self._pf = _factors(model)
        self.num_users = int(self._uf.shape[0])
        self.num_items = int(self._pf.shape[0])
        self.rank = int(self._uf.shape[1])
        cfg = get_config()
        ks = tuple(sorted({int(k) for k in cfg.serve_program_topk
                           if int(k) <= self.num_items}))
        if not ks:
            raise ValueError(
                f"no serve_program_topk value fits num_items="
                f"{self.num_items} (got {cfg.serve_program_topk!r})")
        self._ks = ks
        self.swap_count = 0
        self._ledger_register(self._uf, self._pf)

    def swap_model(self, model) -> None:
        """Atomically install freshly trained factors. Shapes must match
        the resident model (same compiled programs keep serving)."""
        uf, pf = _factors(model)
        if (uf.shape, pf.shape) != (self._uf.shape, self._pf.shape):
            raise ValueError(
                f"swap_model shape mismatch: resident "
                f"({self._uf.shape}, {self._pf.shape}), new "
                f"({uf.shape}, {pf.shape})")
        with self._lock:
            self._uf, self._pf = uf, pf
            self.swap_count += 1
        self._ledger_register(self._uf, self._pf)

    # ---------------------------------------------------------------- policy
    def buckets(self):
        return [(k,) for k in self._ks]

    def validate(self, request):
        p = request.payload
        if not isinstance(p, dict) or "user" not in p:
            return (f"program {self.name!r} needs payload "
                    f"{{'user': int, 'k': int?}}, got {type(p).__name__}")
        user = p["user"]
        if not 0 <= int(user) < self.num_users:
            return (f"user {user} out of range [0, {self.num_users})")
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
        return (f"no bucket fits program='als' k="
                f"{request.payload.get('k')} (k buckets {list(self._ks)})")

    def admission_cost(self, request, bucket):
        return self.num_items * 4

    # ------------------------------------------------------------- mechanism
    def warmup(self) -> int:
        n = 0
        users = {w: jnp.zeros((w,), jnp.int32) for w in self.widths}
        with self._lock:
            uf, pf = self._uf, self._pf
        for (k,) in self.buckets():
            for w in self.widths:
                _als_topk(uf, pf, users[w], k=k)
                n += 1
        return n

    def step(self, bucket, requests):
        (k,) = bucket
        w = self.step_width(len(requests))
        users = np.zeros((w,), np.int32)
        for i, r in enumerate(requests):
            # analyze: ignore[host-sync] — payload ints are host data
            users[i] = int(r.payload["user"])
        with self._lock:
            uf, pf = self._uf, self._pf
        vals, idx = _als_topk(uf, pf, jnp.asarray(users), k=k)
        # analyze: ignore[host-sync] — THE one intentional sync per program
        # step: a one-shot batch retires here and its Result values are
        # host data by contract (the kernel above launched async)
        vals = np.asarray(jax.device_get(vals))
        # analyze: ignore[host-sync] — same fetch, second output
        idx = np.asarray(jax.device_get(idx))
        out = []
        for i, r in enumerate(requests):
            want = int(r.payload.get("k", k))
            out.append({"items": idx[i, :want].copy(),
                        "scores": vals[i, :want].copy()})
        return out
