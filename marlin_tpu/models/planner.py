"""Automatic long-context memory planning via the AOT compile-only channel.

The long-context HBM knobs — ``remat``, ``loss_chunk``, ``mlp_chunk``,
``compute_dtype`` — each trade throughput (or precision) for activation
memory, and their interactions are tabulated in docs/parallelism.md. Picking
them by hand means reading that table; :func:`plan_context` picks them by
asking the TPU compiler directly: it AOT-compiles the REAL training step
(``lm_train_step``) against a compile-only v5e topology (utils/aot.py — no
chip) and escalates knobs, cheapest-throughput-cost first, until
the compiler's own peak-HBM accounting fits the budget.

The budget defaults to *usable* HBM: a measured ``bytes_limit`` when an
on-chip report is supplied, else raw capacity minus a
documented reserve (see :func:`usable_hbm_bytes`) — a "fits" from this
planner is keyed to what the runtime actually grants, not the sticker 16 GiB
(round-4 verdict #2).

No reference analog: the reference's memory knobs are static conf keys
(``marlin.*.basesize``, SURVEY.md §5.6) that the user tunes by trial OOM;
this is only possible because XLA compiles the whole step ahead of time and
reports its memory plan.

Each probe compile costs roughly a minute at 1M tokens (AOT_MEMORY.json
``compile_s``), so the ladder stops at the FIRST fitting rung; planning a
flagship config costs a few minutes once, offline.
"""

from __future__ import annotations

import dataclasses
import json

__all__ = ["plan_context", "ContextPlan", "usable_hbm_bytes",
           "kv_page_bytes", "request_pages"]

GIB = 1024 ** 3

# Headroom policy (docs/parallelism.md): when no measured usable-HBM figure
# exists, reserve this much of raw capacity for the runtime/framework — the
# v5e reserves a slice of its 16 GiB that compile-time accounting never sees.
DEFAULT_RESERVE_BYTES = 3 * GIB // 4  # 0.75 GiB


def usable_hbm_bytes(total_bytes: int = 16 * GIB,
                     onchip_report: str | None = None) -> int:
    """The planning budget: the ``bytes_limit`` of ``onchip_report`` (a JSON
    file recording what the TPU runtime actually grants,
    ``device.memory_stats()["bytes_limit"]``) when one is given, else
    ``total_bytes`` minus the documented reserve."""
    if onchip_report is not None:
        try:
            with open(onchip_report) as f:
                limit = int(json.load(f).get("bytes_limit", 0))
            if limit > 0:
                return limit
        except (FileNotFoundError, ValueError):
            pass
    return total_bytes - DEFAULT_RESERVE_BYTES


def kv_page_bytes(params: dict, heads, page_len: int,
                  compute_dtype=None, kind: str = "full") -> int:
    """Bytes of ONE KV page across every layer: layers x {k,v} x page_len x
    kv_heads x dh in the compute dtype. The paged serving engine's admission
    unit — a request is charged :func:`request_pages` x this, the *actual*
    memory its cache rows can ever pin, not the bucket's worst case
    (docs/serving.md). For a
    :class:`~marlin_tpu.models.hybrid.ModelSpec` a page id names a page in
    the layers of one ``kind`` only (``full``: the global class, whose
    latent layers hold ONE array of ``entry_width`` values a position where
    the others hold K and V per KV head; ``sliding``: the window class), so
    each class has its own page bytes
    (:meth:`~marlin_tpu.models.hybrid.ModelSpec.page_values`)."""
    import jax.numpy as jnp

    from .hybrid import ModelSpec
    from .transformer import _n_layers

    if isinstance(heads, ModelSpec):
        dt = jnp.dtype(compute_dtype or heads.compute_dtype)
        return heads.page_values(kind, page_len) * dt.itemsize

    d = params["emb"].shape[1]
    dh = d // heads
    kv_dim = params["l0"]["wk"].shape[1]  # kv_heads * dh (GQA-aware)
    dt = jnp.dtype(compute_dtype) if compute_dtype else params["emb"].dtype
    return _n_layers(params) * 2 * page_len * (kv_dim // dh) * dh \
        * dt.itemsize


def request_pages(prompt_len: int, steps: int, page_len: int,
                  ring: int | None = None) -> int:
    """KV pages one request can ever write: cache positions run
    ``[0, prompt_len + steps - 1)`` (the final emitted token is never
    decoded from, so its K/V is never stored), rounded up to whole pages.
    This is the paged admission charge AND the allocation size — charging
    what will be written is what guarantees page allocation can never fail
    under an admission-bounded load (serving/kvpool.py). With ``ring`` (the
    window class of a model with sliding layers) the request pins at most
    that many pages: positions behind the window are overwritten in place."""
    if prompt_len < 1 or steps < 1 or page_len < 1:
        raise ValueError(f"prompt_len/steps/page_len must be >= 1, got "
                         f"{(prompt_len, steps, page_len)}")
    pages = -(-(prompt_len + steps - 1) // page_len)
    return pages if ring is None else min(pages, ring)


@dataclasses.dataclass(frozen=True)
class ContextPlan:
    """The planner's verdict: ``model`` is the escalated TransformerLM ready
    to train; ``trail`` records every rung probed as
    ``(knobs, peak_bytes | None, fits, note)``."""

    model: object  # TransformerLM
    knobs: dict
    peak_bytes: int | None
    fits: bool
    budget_bytes: int
    seq: int
    trail: tuple

    @property
    def peak_gib(self) -> float | None:
        return None if self.peak_bytes is None else round(
            self.peak_bytes / GIB, 3)

    def describe(self) -> str:
        head = (f"seq={self.seq}: {'fits' if self.fits else 'DOES NOT FIT'} "
                f"{self.peak_gib} GiB of {round(self.budget_bytes / GIB, 3)} "
                f"GiB usable with {self.knobs or 'no knobs'}")
        rungs = "\n".join(
            f"  probed {k or '{}'}: "
            f"{'?' if p is None else round(p / GIB, 3)} GiB"
            f"{' (fits)' if f else ''}{' — ' + n if n else ''}"
            for k, p, f, n in self.trail)
        return head + "\n" + rungs


def _compiled_peak(model, seq: int, mesh) -> tuple[int | None, str]:
    """(peak_bytes, note) for one lm_train_step compile on the AOT topology.
    An over-HBM rejection is a result: the compiler names its own usage,
    which becomes the rung's peak (same contract as tools/aot_report._try)."""
    from ..config import config_context
    from ..utils.aot import parse_hbm_oom, trace_lm_train_step

    try:
        with config_context(pallas_interpret=False):
            compiled = trace_lm_train_step(model, seq, mesh) \
                .lower().compile()
        return compiled.memory_analysis().peak_memory_in_bytes, ""
    except Exception as e:
        needed = parse_hbm_oom(e)
        if needed is not None:
            return needed, "compiler rejected (>HBM)"
        return None, "compile failed: " + str(e).split("\n")[0][:160]


def _ladder(model, seq: int):
    """Cumulative knob escalation, cheapest throughput cost first (the
    docs/parallelism.md ordering): remat trades FLOPs, the chunk knobs trade
    scan overhead, bf16 trades activation precision, and host-offloaded
    residuals trade PCIe traffic (last — it only nets out for
    residual-dominated shapes). Rungs already set on the user's config are
    skipped (they cannot un-set)."""
    rungs = [{}]
    acc = {}
    chunk = max(1, min(16384, seq))
    for knob, val in (("remat", True), ("loss_chunk", chunk),
                      ("mlp_chunk", chunk), ("compute_dtype", "bfloat16"),
                      ("offload_residuals", True)):
        if getattr(model, knob, None) in (None, False):
            acc = dict(acc, **{knob: val})
            rungs.append(dict(acc))
    return rungs


# smallest compile-only v5e topology holding each supported mesh size
_TOPOLOGY_FOR_CHIPS = {1: "v5e:2x2", 2: "v5e:2x2", 4: "v5e:2x2",
                       8: "v5e:2x4", 16: "v5e:4x4"}


def plan_context(seq: int, model, hbm_budget: int | None = None,
                 chips: int = 1, topology_name: str | None = None,
                 measure=None):
    """Pick the cheapest knob set under which ``model`` trains ``seq`` tokens
    within ``hbm_budget`` bytes *per chip* on a ``chips``-device ring, by
    compiler accounting.

    ``model`` is a :class:`~marlin_tpu.models.transformer.TransformerLM`
    (its existing knob settings are respected and never weakened).
    ``hbm_budget`` defaults to :func:`usable_hbm_bytes`. ``chips`` > 1
    compiles the SAME sharded program the multi-chip runtime executes (the
    ring over a real v5e topology; ``memory_analysis`` is per device), so a
    fitting plan certifies the sequence-parallel deployment, not a proxy.
    ``measure`` overrides the probe (tests); the default compiles on the
    compile-only topology and needs libtpu
    (:func:`marlin_tpu.utils.aot.tpu_topology`).

    Returns a :class:`ContextPlan`; when nothing fits, the plan carries the
    lowest-peak rung with ``fits=False`` — its ``peak_bytes / budget`` ratio
    is roughly the factor more chips the mesh needs (sequence memory shards
    ~linearly over the ring; AOT_MEMORY.json ``lct_long_4chip``), or see the
    host-offload path in docs/parallelism.md."""
    budget = usable_hbm_bytes() if hbm_budget is None else int(hbm_budget)
    if measure is None:
        from ..utils.aot import topology_mesh

        if topology_name is None:
            try:
                topology_name = _TOPOLOGY_FOR_CHIPS[chips]
            except KeyError:
                raise ValueError(
                    f"chips must be one of {sorted(_TOPOLOGY_FOR_CHIPS)} "
                    "(or pass topology_name explicitly)") from None
        mesh = topology_mesh(("rows",), (chips,), topology_name=topology_name)

        def measure(m):
            return _compiled_peak(m, seq, mesh)

    trail = []
    best = None  # (peak, knobs, model)
    for knobs in _ladder(model, seq):
        candidate = dataclasses.replace(model, **knobs)
        peak, note = measure(candidate)
        fits = peak is not None and peak <= budget
        trail.append((knobs, peak, fits, note))
        if peak is not None and (best is None or peak < best[0]):
            best = (peak, knobs, candidate)
        if fits:
            return ContextPlan(model=candidate, knobs=knobs, peak_bytes=peak,
                               fits=True, budget_bytes=budget, seq=seq,
                               trail=tuple(trail))
    peak, knobs, candidate = best if best else (None, {}, model)
    return ContextPlan(model=candidate, knobs=knobs, peak_bytes=peak,
                       fits=False, budget_bytes=budget, seq=seq,
                       trail=tuple(trail))
