"""Shape bucketing and per-bucket claim queues for the serving engine.

The serving programs compile one XLA executable per *shape* — slot width B,
padded prompt P, decode steps S are all baked in. Serving traffic is
ragged, so without discipline every new shape pays a fresh multi-second
compile. The discipline here:

- **Buckets** — a small static set of ``(P_bucket, steps_bucket)`` pairs. A
  request pads its prompt up to the smallest fitting ``P_bucket``; rows
  retire at their *requested* steps (the bucket only sizes the cache
  extent).
- **Fixed slot width** — every bucket's row set is exactly ``max_batch``
  wide, and so is the one decode call the live rows of all buckets share
  (the rows it does not fill run masked-harmless dummies), so B never
  varies and the compile count is bounded by the bucket set (a prefill
  program each, ONE decode program), not the traffic pattern.
- **Claim queues** — :class:`BatchFormer` keeps one priority-ordered FIFO
  per bucket; :meth:`BatchFormer.take_for_bucket` hands freed rows the best
  pending request immediately (prefill-on-admit — higher ``priority``
  first, FIFO among equals; sampling knobs never partition anything, they
  are per-row traced vectors in the decode programs). The gang scheduler's
  batch-forming machinery (sampling-knob grouping, ``max_wait`` ripening,
  ``next_batch``) was retired with it in PR 8 — paging superseded the gang
  fallback.

A bucket's row state (block tables, cursors, sampling vectors) is
:class:`~.kvpool.PagedGroup`; :func:`~.kvpool.decode_inputs` packs the live
rows of every bucket's group into one decode call; warm-up of the programs
is :func:`~.kvpool.warmup_paged`. The pool sizes by page arithmetic:
``models/planner.kv_page_bytes`` × ``serve_num_pages`` is its steady-state
footprint, whatever the bucket set.
"""

from __future__ import annotations

import collections
from typing import Iterable, Sequence

__all__ = ["normalize_buckets", "pick_bucket", "BatchFormer"]

Bucket = tuple[int, int]  # (P_bucket, steps_bucket)


def normalize_buckets(buckets: Iterable[Sequence[int]]) -> tuple[Bucket, ...]:
    """Validate and sort a bucket set ascending by (P, steps) — the order
    :func:`pick_bucket` scans, so "smallest fitting bucket" is first hit."""
    out = []
    for b in buckets:
        p, s = int(b[0]), int(b[1])
        if p < 1 or s < 1:
            raise ValueError(f"bucket dims must be >= 1, got {(p, s)}")
        out.append((p, s))
    if not out:
        raise ValueError("at least one (P, steps) bucket is required")
    if len(set(out)) != len(out):
        raise ValueError(f"duplicate buckets in {out}")
    return tuple(sorted(out))


def pick_bucket(prompt_len: int, steps: int,
                buckets: Sequence[Bucket]) -> Bucket | None:
    """The smallest bucket holding a ``prompt_len``-token prompt generating
    ``steps`` tokens, or None when nothing fits (an admission rejection —
    better than a surprise compile)."""
    for p, s in buckets:
        if prompt_len <= p and steps <= s:
            return (p, s)
    return None


class _Group:
    """One bucket's stream of pending entries, kept in dispatch order:
    higher priority first, FIFO among equals (stable sort on a monotonic
    sequence number keeps arrival order)."""

    def __init__(self):
        self.entries: list = []  # (-priority, seq, entry)

    def add(self, entry, seq: int) -> None:
        self.entries.append((-entry.request.priority, seq, entry))
        self.entries.sort(key=lambda t: t[:2])

    def take(self, n: int):
        taken = [e for _, _, e in self.entries[:n]]
        del self.entries[:n]
        return taken


class BatchFormer:
    """One priority-ordered claim queue per bucket. Sampling knobs never
    partition anything — they are per-row traced vectors in the decode
    programs, so ANY mix shares a step (the gang scheduler's sampling-knob
    grouping and ``max_wait`` ripening retired with it, PR 8; ``max_wait``
    is still accepted and ignored so old call sites don't break). Not
    thread-safe by itself — the engine calls it under its own condition
    lock (one mutator, one reader)."""

    def __init__(self, buckets: Sequence[Bucket], max_batch: int,
                 max_wait: float = 0.0):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_wait < 0:
            raise ValueError(f"max_wait must be >= 0, got {max_wait}")
        self.buckets = normalize_buckets(buckets)
        self.max_batch = max_batch
        self.max_wait = max_wait  # legacy knob: nothing ripens anymore
        self._groups: dict[Bucket, _Group] = collections.defaultdict(_Group)
        self._seq = 0

    def add(self, entry) -> None:
        """File one admitted entry under its bucket. ``entry.bucket`` and
        ``entry.enq_t`` were set at admission (engine.submit)."""
        self._groups[entry.bucket].add(entry, self._seq)
        self._seq += 1

    def pending(self) -> int:
        return sum(len(g.entries) for g in self._groups.values())

    def take_all(self) -> list:
        """Drain every pending entry (close() path — they get ShuttingDown
        results, never a decode)."""
        out = []
        for g in self._groups.values():
            out.extend(g.take(len(g.entries)))
        return out

    def pending_buckets(self) -> set:
        """Buckets that currently have pending entries (which groups might
        claim work this iteration)."""
        return {b for b, g in self._groups.items() if g.entries}

    def take_for_bucket(self, bucket: Bucket, n: int) -> list:
        """Up to ``n`` entries bound for ``bucket`` in dispatch order —
        the prefill-on-admit path: a freed row takes the best pending
        request immediately."""
        return self._groups[bucket].take(n) if bucket in self._groups else []
