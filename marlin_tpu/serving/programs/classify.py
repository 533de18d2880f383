"""Batched classification / embedding scoring as a BucketProgram.

Serves either of the paper's supervised models online: a
:class:`~marlin_tpu.ml.logistic_regression.LogisticRegressionModel`
(intercept-first weight vector) or an MLP parameter dict from
:func:`~marlin_tpu.ml.neural_network.mlp_init` — a request carries one
feature vector (payload ``{"x": (d,) floats}``) and gets back the model's
probabilities plus an argmax/threshold label. One program bucket (the model
is the shape), padded batch widths shared with every other program, and the
same atomic :meth:`ClassifyProgram.swap_model` hot-update contract as ALS.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...ml.neural_network import mlp_forward
from . import register_program
from .base import BucketProgram

__all__ = ["ClassifyProgram"]


@jax.jit
def _logreg_proba(weights, x):
    return jax.nn.sigmoid(weights[0] + x @ weights[1:])


@functools.partial(jax.jit, static_argnames=("activation",))
def _mlp_proba(params, x, activation: str):
    return mlp_forward(params, x, activation)


def _model_arrays(model, activation):
    """(kind, params, feature_dim, num_outputs) for either model family."""
    w = getattr(model, "weights", model)
    if isinstance(w, dict):
        params = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        # mlp_forward indexes w0..wN by position; validate the contract here
        # so a typo'd dict fails at construction, not inside a traced call
        for i in range(len(params)):
            if f"w{i}" not in params:
                raise ValueError(
                    f"MLP params must be w0..w{len(params) - 1}, got "
                    f"{sorted(params)}")
        dim = int(params["w0"].shape[0])
        n_out = int(params[f"w{len(params) - 1}"].shape[1])
        # abstract trace: rejects an unknown activation at construction
        jax.eval_shape(lambda p, xx: mlp_forward(p, xx, activation),
                       params, jnp.zeros((1, dim), jnp.float32))
        return "mlp", params, dim, n_out
    w = jnp.asarray(w, jnp.float32).reshape(-1)
    if w.shape[0] < 2:
        raise ValueError(f"logreg weights need [intercept, w...], got "
                         f"shape {w.shape}")
    return "logreg", w, int(w.shape[0]) - 1, 1


@register_program
class ClassifyProgram(BucketProgram):
    """feature vector → class probabilities over a resident model."""

    name = "classify"
    resource_unit = "one padded feature row: feature_dim x 4 bytes"

    def __init__(self, model, activation: str = "sigmoid"):
        super().__init__()
        self._activation = activation
        self._kind, self._params, self.feature_dim, self.num_outputs = \
            _model_arrays(model, activation)
        self.swap_count = 0
        self._ledger_register(self._params)

    def swap_model(self, model) -> None:
        """Atomically install new weights of the same shape (same compiled
        programs keep serving; a shape change is a new program)."""
        kind, params, dim, n_out = _model_arrays(model, self._activation)
        if (kind, dim, n_out) != (self._kind, self.feature_dim,
                                  self.num_outputs):
            raise ValueError(
                f"swap_model shape mismatch: resident {self._kind} "
                f"d={self.feature_dim} out={self.num_outputs}, new {kind} "
                f"d={dim} out={n_out}")
        with self._lock:
            self._params = params
            self.swap_count += 1
        self._ledger_register(self._params)

    # ---------------------------------------------------------------- policy
    def buckets(self):
        return [()]  # the model is the shape; width is the only batch axis

    def validate(self, request):
        p = request.payload
        x = p.get("x") if isinstance(p, dict) else p
        if x is None:
            return (f"program {self.name!r} needs payload "
                    f"{{'x': ({self.feature_dim},) floats}}")
        x = np.asarray(x, np.float32).reshape(-1)
        if x.shape[0] != self.feature_dim:
            return (f"feature vector has {x.shape[0]} dims, model wants "
                    f"{self.feature_dim}")
        return None

    def pick_bucket(self, request):
        return ()

    def admission_cost(self, request, bucket):
        return self.feature_dim * 4

    # ------------------------------------------------------------- mechanism
    def _fwd(self, params, x):
        if self._kind == "logreg":
            return _logreg_proba(params, x)
        return _mlp_proba(params, x, self._activation)

    def warmup(self) -> int:
        n = 0
        with self._lock:
            params = self._params
        for w in self.widths:
            x = jnp.zeros((w, self.feature_dim), jnp.float32)
            self._fwd(params, x)
            n += 1
        return n

    def step(self, bucket, requests):
        w = self.step_width(len(requests))
        x = np.zeros((w, self.feature_dim), np.float32)
        for i, r in enumerate(requests):
            p = r.payload
            # analyze: ignore[host-sync] — payload features are host data
            x[i] = np.asarray(p.get("x") if isinstance(p, dict) else p,
                              np.float32).reshape(-1)
        with self._lock:
            params = self._params
        # analyze: ignore[host-sync] — THE one intentional sync per program
        # step: the one-shot batch retires here with host Result values
        proba = np.asarray(jax.device_get(self._fwd(params, jnp.asarray(x))))
        out = []
        for i, _ in enumerate(requests):
            row = proba[i]
            if row.ndim == 0 or (row.ndim == 1 and row.shape[0] == 1):
                p1 = float(np.reshape(row, ()) if row.ndim == 0 else row[0])
                out.append({"proba": p1, "label": int(p1 >= 0.5)})
            else:
                # analyze: ignore[host-sync] — row is already host numpy
                out.append({"proba": row.copy(),
                            "label": int(np.argmax(row))})  # analyze: ignore[host-sync] — host numpy
        return out
