"""Paged KV-cache pool: block-table paging + copy-on-write prefix sharing.

A cache row sized for its bucket's worst case wastes HBM linearly for a
short request in a long bucket, and admission would have to budget by
bucket. So the cache is the classic paged design: ONE device-resident page
slab per engine
(:func:`~marlin_tpu.models.transformer.init_kv_pages` — ``(num_pages,
page_len, kvh, dh)`` per layer, shared by every bucket; a
:class:`~marlin_tpu.models.hybrid.ModelSpec` model's K and V slabs are
``(num_pages, page_len, kvh * dh)``, the same bytes with a token's heads in
one row, the form its decode kernel reads fastest: nothing here goes by a
slab's rank, only by its first axis, its bytes and its arrays) plus
host-side bookkeeping per row:

- **Block tables** — each live row holds an ordered list of page ids
  covering its positions; the decode program gathers by table, the chunked
  prefill program scatters by table
  (:func:`~marlin_tpu.models.transformer.lm_decode_paged` /
  :func:`~marlin_tpu.models.transformer.lm_prefill_paged`). Rows are kept
  per bucket (:class:`PagedGroup`), but ONE decode call carries the live
  rows of every bucket (:func:`decode_inputs`): each row's table is laid
  into the widest bucket's width, the rest of it the dummy page.
- **Free-list allocation + refcounts** — a request allocates exactly
  :func:`~marlin_tpu.models.planner.request_pages` pages (what it can ever
  write); every retirement path releases them exactly once; page 0 is a
  permanently-pinned dummy that absorbs out-of-extent gathers/scatters.
- **Copy-on-write prefix sharing** — completed FULL pages of prompt tokens
  are cached under a rolling hash (page k's key folds page k-1's key, so a
  key names an entire prefix, not one page's content): a later request
  whose prompt starts with the same pages takes a reference instead of
  re-prefilling — the dominant real-traffic shape, a common system prompt
  prefilled once. The page holding the prompt's LAST token is never shared
  (it is re-prefilled so the first-token logits exist, and decode writes
  continue into it), so in steady state shared pages are read-only by
  construction; :meth:`PagedKVPool.ensure_writable` still implements the
  full COW contract — a writer to a page with other referents gets a fresh
  page and a device :func:`~marlin_tpu.models.transformer.kv_page_copy` —
  as the safety net the engine runs before every write. Cached pages are
  LRU-evicted (leaf-first — an entry with cached children or live readers
  is not evictable) when allocation needs room.

Allocation invariant (why :meth:`alloc` cannot fail under the auto-sized
pool): pages are allocated only when a request claims a ROW, rows are
bounded by the slot set (``max_batch`` per bucket), each row allocates at
most its bucket's page extent, and cache-only pages are LRU-evictable —
so the :func:`auto_num_pages` default (every bucket at full width, plus
slack) always has room, whatever the queue depth. A hand-set smaller
``serve_num_pages`` can run out under full occupancy; the engine guards
the call either way (a failed alloc retries/errors one request, never the
worker).

**Two classes of page** (a :class:`~marlin_tpu.models.hybrid.ModelSpec`
with sliding-window layers): the full-attention layers' slabs are indexed by
the page ids above (a row's *global* table covers every position); the
sliding layers' slabs are a second, smaller id space with its own free list
and refcounts, of which a row holds a fixed **ring** of
:func:`~marlin_tpu.models.hybrid.window_ring_pages` pages (position ``p``
lives in ring slot ``(p // page_len) % ring``: a page that falls behind the
window is overwritten in place, so a row pins O(window + chunk) positions
there whatever its length). Admission charges each class for what the row
can pin; :meth:`PagedKVPool.audit` balances both. Window pages are never
shared: with such a model the prefix cache is off and the migration entry
points raise.

**A page that is one array** (a ``ModelSpec``'s latent-attention layers): a
layer's slab is a tuple of arrays indexed by the same page id, ``(k, v)``
for the layers above and ONE array ``(num_pages, page_len, entry_width)``
for a latent layer (:func:`~marlin_tpu.models.hybrid.init_kv_pages`).
Nothing here assumes the pair: the page-copy program, the host fetch and
flush, and the migration blobs walk each layer's arrays in order, and
:func:`~marlin_tpu.models.planner.kv_page_bytes` prices what a page id
holds. Latent pages are of the global class, so they are shared through the
prefix cache and migrate like any other.

**A state slot beside the pages** (a ``ModelSpec`` with recurrent mixers:
state-space ones beside attention, or delta-rule ones in layers that have NO
attention and so no page): such a layer's memory of the past is not keys
and values in pages but, per row, a recurrent state of fixed size and the
tail of a short convolution (:func:`~marlin_tpu.models.hybrid
.init_kv_pages`: two more arrays a layer, after its pages if it has any). A
row holds ONE state slot, an index of this pool
(:meth:`PagedKVPool.alloc_state`) that serves every such layer, taken at
admission with the row's pages and freed with them
(:meth:`PagedKVPool.release_row`); slot 0 is the dummy that padded decode
rows scribble on. It neither grows nor pages. Nothing zeroes a slot when it
changes hands: a row's first prefill chunk enters with a zero state whatever
the slot holds, and that program is behind every earlier call in the stream.
A :class:`PagedGroup` keeps a row's state slot as ``state_ids[slot]``: a
pool-level id, NOT the row's slot (its place) in the group. The migration
entry points raise for such a model (:meth:`PagedKVPool._refuse_private`).

**Snapshots: a prefix shared WITH its state** (``snapshot_slots`` > 0). A
shared page is no use to a row without the recurrent state at the prefix's
end, so for a model with state the prefix cache works only where that state
was kept. The state arrays hold ``snapshot_slots`` further slots after the
rows' (same slab, same slot size); a SNAPSHOT is a copy row slot ->
snapshot slot (:func:`~marlin_tpu.models.hybrid.state_slot_copy`, one
program, dispatched in the stream behind the chunk that wrote the state).
Its life:

- *taken* by the engine behind a prefill chunk that ends on the row's
  deepest shareable page boundary, or on the boundary up to which its
  prompt's pages were found cached with no snapshot to enter from
  (:meth:`PagedKVPool.snapshot_due`; the row owns it meanwhile:
  :attr:`PagedGroup.snapshots`). A pool with no
  snapshot slot to give (none free, none cached to evict) skips it: the row
  is served all the same, its pages are merely not usable by others;
- *published* with the row's pages when its prefill has landed
  (:meth:`PagedKVPool.insert_prefix`): the cache entry of the page that ends
  at the boundary now owns it (a boundary that already has one frees the
  newcomer);
- *hit* at admission (:meth:`PagedKVPool.match_prefix_state`): the match
  stops at the deepest boundary WITH a snapshot, never hands out a page
  past it, and the engine copies the snapshot into the row's slot ahead of
  the row's first chunk, which starts at that boundary. A chain whose deepest snapshot is gone is a shorter
  hit (or a miss), never an error;
- *evicted* with its cache entry, or before it when a new snapshot needs
  the slot: the oldest that was never hit first, then the least recently
  hit (newest-first among the never-hit ones lost a new prefix's snapshot
  to the next prompt's own boundary three times in four, and the prefix was
  prefilled again and again: PERF.md, PR 42). It is never referenced by a
  row after the copy was dispatched, so it needs no reference count.

Everything here is host-side numpy/stdlib except the three compiled
programs it drives; single-threaded by contract (only the engine worker
touches a pool).
"""

from __future__ import annotations

import hashlib
import json
import struct
import zlib
from collections import OrderedDict

import numpy as np

__all__ = ["PagedKVPool", "PagedGroup", "PagePoolExhausted",
           "MigrationCorruptError", "auto_num_pages", "warmup_paged",
           "decode_inputs", "decode_pages"]


class PagePoolExhausted(RuntimeError):
    """alloc() found fewer free+evictable pages than requested."""


class MigrationCorruptError(RuntimeError):
    """A migration blob failed structural or CRC validation on import —
    truncation, bit flips, or a geometry mismatch between pools. Import
    never partially applies a corrupt blob."""


# Migration wire format (PR 12): the MarlinChunk idiom — a flat sequence of
# 32-byte-header chunks, each body independently CRC32-framed so a torn or
# bit-flipped blob ALWAYS raises on import instead of resurrecting garbage
# KV state on the target replica.
#   header: magic "MGRT" | crc32(body) | kind | body_len | 12 pad bytes
_MIG_MAGIC = b"MGRT"
_MIG_HDR = struct.Struct("<4sIIQ12x")  # 32 bytes
_MIG_META = 1      # JSON metadata (geometry + per-row/per-entry manifest)
_MIG_ROW = 2       # one row's page contents, layers in order, each layer's
#                    arrays in order (k then v; a latent layer's one)
_MIG_PREFIX = 3    # prefix-cache pages (one body for the whole entry set)


def _mig_frame(kind: int, body: bytes) -> bytes:
    return _MIG_HDR.pack(_MIG_MAGIC, zlib.crc32(body) & 0xFFFFFFFF, kind,
                         len(body)) + body


def _mig_chunks(blob: bytes) -> list[tuple[int, bytes]]:
    """Split and validate a migration blob; raises on any corruption."""
    out = []
    off = 0
    n = len(blob)
    while off < n:
        if n - off < _MIG_HDR.size:
            raise MigrationCorruptError(
                f"truncated chunk header at offset {off}")
        magic, crc, kind, length = _MIG_HDR.unpack_from(blob, off)
        if magic != _MIG_MAGIC:
            raise MigrationCorruptError(
                f"bad chunk magic {magic!r} at offset {off}")
        off += _MIG_HDR.size
        body = blob[off:off + length]
        if len(body) != length:
            raise MigrationCorruptError(
                f"truncated chunk body at offset {off}: "
                f"need {length} bytes, have {len(body)}")
        if zlib.crc32(body) & 0xFFFFFFFF != crc:
            raise MigrationCorruptError(
                f"chunk CRC mismatch at offset {off}")
        out.append((kind, body))
        off += length
    return out


def _mig_default(o):
    """json.dumps default: numpy scalars/arrays from group vectors."""
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON-serializable: {type(o).__name__}")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def group_chunk(bucket, page_len: int, prefill_chunk: int) -> int:
    """The compiled prefill-chunk width of one bucket, in tokens: whole
    pages, never wider than the prompt extent (a narrow bucket compiles the
    smaller program), and CAPPED below the per-iteration token budget
    (serve_prefill_chunk) — the program's cost is fixed at its width
    whatever the real token count, so a wide program makes a prefix-hit
    row's short tail (the prefix-cache win) as expensive as a full prefill;
    the engine instead runs several small chunks per iteration up to the
    budget."""
    cap = max(64, 4 * page_len)
    return min(_round_up(max(1, prefill_chunk), page_len),
               _round_up(bucket[0], page_len), _round_up(cap, page_len))


def auto_window_pages(buckets, max_batch: int, ring: int) -> int:
    """The default size of the window class: every slot of every bucket
    holds one ring, plus the dummy."""
    return 1 + len(list(buckets)) * max_batch * ring


def auto_num_pages(buckets, max_batch: int, page_len: int) -> int:
    """The default pool size (``serve_num_pages=0``): every bucket's full
    slot width at its full extent, so a full slot set always fits, plus
    one slack page per slot (chunk scatter spill) and the dummy page 0.
    Short requests use fewer pages than this budget assumes; the surplus is
    what the prefix cache lives in."""
    pages = 1  # the dummy
    for p, s in buckets:
        pages += max_batch * (-(-(p + s) // page_len) + 1)
    return pages


#: "no snapshot" where a snapshot slot's id is expected (slot 0 is the
#: rows' dummy, never a snapshot's)
_NO_SNAPSHOT = 0


class _CacheEntry:
    __slots__ = ("page", "parent", "children")

    def __init__(self, page: int, parent: bytes | None):
        self.page = page
        self.parent = parent
        self.children = 0


class PagedKVPool:
    """Host-side owner of one engine's page slab (see module docstring).

    ``pages`` is the device slab dict; the engine replaces it after every
    donated program call. Counters (``hits``/``misses``/``cow_copies``/
    ``evictions``) feed the serving metrics."""

    def __init__(self, params: dict, heads, num_pages: int,
                 page_len: int, compute_dtype: str | None = None,
                 prefix_cache: bool = True, window_pages: int = 0,
                 ring: int = 0, state_slots: int = 0,
                 snapshot_slots: int = 0):
        from ..models.hybrid import ModelSpec
        from ..models.planner import kv_page_bytes
        from ..models.transformer import init_kv_pages

        self.page_len = int(page_len)
        self.num_pages = int(num_pages)
        self.compute_dtype = compute_dtype
        windowed = isinstance(heads, ModelSpec) and heads.has_window
        stateful = isinstance(heads, ModelSpec) and heads.has_state
        #: recurrent-state slots, the dummy slot 0 among them (0: the model
        #: has no state-space mixer)
        self.state_slots = int(state_slots) if stateful else 0
        self._sfree = list(range(self.state_slots - 1, 0, -1))
        #: snapshot slots, after the rows' in the same arrays (ids
        #: ``state_slots .. state_slots + snapshot_slots - 1``); with them a
        #: model with state shares prefixes (module docstring)
        self.snapshot_slots = int(snapshot_slots) if (
            stateful and prefix_cache and not windowed) else 0
        self._snapfree = list(range(
            self.state_slots + self.snapshot_slots - 1,
            self.state_slots - 1, -1))
        #: cache key -> the snapshot slot that holds the state at the end
        #: of that entry's page, least recently published or hit first; and
        #: the keys whose snapshot a row has entered from at least once
        self._snaps: OrderedDict[bytes, int] = OrderedDict()
        self._snap_hit: set[bytes] = set()
        self._spec = heads if stateful else None
        #: a snapshot that holds no more than the page it stands behind (a
        #: short convolution's tails: tens of kilobytes) is taken behind
        #: EVERY chunk (:meth:`snapshot_due`)
        self._snapshot_cheap = stateful and heads.state_slot_bytes(
            compute_dtype) <= kv_page_bytes(params, heads, self.page_len,
                                            compute_dtype)
        self.snapshots_taken = 0
        self.snapshot_evictions = 0
        #: pages of the window class (0: the model has no sliding layer)
        #: and the ring a row holds of them
        self.window_pages = int(window_pages) if windowed else 0
        self.ring = int(ring) if windowed else 0
        if windowed and self.ring < 1:
            raise ValueError("a model with sliding layers needs ring >= 1")
        self.pages = init_kv_pages(params, num_pages, page_len, heads,
                                   compute_dtype, self.window_pages,
                                   self.state_slots + self.snapshot_slots)
        self._wfree = list(range(self.window_pages - 1, 0, -1))
        self._wref = np.zeros(self.window_pages, np.int32)
        if windowed:
            self._wref[0] = 1  # the window class's own dummy
            prefix_cache = False  # a window page is never shared
        if stateful and not self.snapshot_slots:
            prefix_cache = False  # nor a page without the state at its end
        # pop() hands out ascending ids; page 0 never enters the list
        self._free = list(range(num_pages - 1, 0, -1))
        self._ref = np.zeros(num_pages, np.int32)
        self._ref[0] = 1  # the dummy page is pinned forever
        self._cache: OrderedDict[bytes, _CacheEntry] = OrderedDict()
        self.prefix_cache_enabled = bool(prefix_cache)
        self.hits = 0
        self.misses = 0
        self.cow_copies = 0
        self.evictions = 0

    # ------------------------------------------------------------- capacity

    @property
    def capacity(self) -> int:
        """Allocatable pages (everything but the dummy)."""
        return self.num_pages - 1

    def free_count(self) -> int:
        return len(self._free)

    def used_count(self) -> int:
        """Pages held by rows and/or the prefix cache."""
        return self.capacity - len(self._free)

    def shared_count(self) -> int:
        """Pages with more than one referent (cache + row, or row + row)."""
        return int((self._ref[1:] > 1).sum())

    def cached_count(self) -> int:
        return len(self._cache)

    def stats(self) -> dict:
        out = {"total": self.capacity, "used": self.used_count(),
               "shared": self.shared_count(),
               "cached": self.cached_count(), "hits": self.hits,
               "misses": self.misses, "cow_copies": self.cow_copies,
               "evictions": self.evictions}
        if self.window_pages:
            out.update(window_total=self.window_pages - 1,
                       window_used=self.window_used_count())
        if self.state_slots:
            out.update(state_total=self.state_slots - 1,
                       state_used=self.state_used_count())
        if self.snapshot_slots:
            out.update(snapshot_total=self.snapshot_slots,
                       snapshots_held=self.snapshots_held(),
                       snapshots_taken=self.snapshots_taken,
                       snapshot_evictions=self.snapshot_evictions)
        return out

    # ------------------------------------------------------ the window class

    def window_used_count(self) -> int:
        return max(self.window_pages - 1, 0) - len(self._wfree)

    def alloc_window(self, n: int) -> list[int]:
        """``n`` fresh pages of the window class (a row's ring). Nothing is
        evictable there: :class:`PagePoolExhausted` when too few are free."""
        if len(self._wfree) < n:
            raise PagePoolExhausted(
                f"need {n} window pages, {len(self._wfree)} free "
                f"({self.window_used_count()}/{self.window_pages - 1} used)")
        out = [self._wfree.pop() for _ in range(n)]
        for p in out:
            self._wref[p] = 1
        return out

    def release_window(self, pages) -> None:
        """A retiring row's ring goes back to the window class's free list
        (the window twin of :meth:`release`; never shared, so one referent)."""
        for p in pages or ():
            self._wref[p] -= 1
            assert self._wref[p] == 0, f"window page {p} released twice"
            self._wfree.append(int(p))

    def _refuse_private(self, what: str) -> None:
        """Migration moves pages; what a row holds besides (a window ring,
        a recurrent-state slot) is not serialized, and the pages are no use
        without it. (Sharing: a window ring is never shared; a state is, by
        snapshot, through the prefix cache alone.)"""
        if self.window_pages:
            raise NotImplementedError(
                f"{what} is not built for a model with sliding-window "
                f"layers: a window layer's pages are a per-row ring that is "
                f"neither shared nor serialized")
        if self.state_slots:
            raise NotImplementedError(
                f"{what} is not built for a model with recurrent mixers: a "
                f"row's pages are no use without the recurrent state at "
                f"their end, and neither a state slot nor a snapshot is "
                f"serialized")

    # ------------------------------------------------------- the state slots

    def state_used_count(self) -> int:
        return max(self.state_slots - 1, 0) - len(self._sfree)

    def alloc_state(self) -> int:
        """One free state slot for a row being admitted (0 where the model
        has none). Whatever its last row left in it stays there: the row's
        first prefill chunk enters with zeros. :class:`PagePoolExhausted`
        when every slot is held."""
        if not self.state_slots:
            return 0
        if not self._sfree:
            raise PagePoolExhausted(
                f"need a state slot, all {self.state_slots - 1} are held")
        return self._sfree.pop()

    def release_state(self, state_id: int) -> None:
        """A retiring row's state slot goes back to the free list."""
        if state_id:
            assert state_id not in self._sfree, \
                f"state slot {state_id} released twice"
            self._sfree.append(int(state_id))

    # --------------------------------------------------------- the snapshots

    def snapshot_due(self, chunk_end: int, chunk: int, prompt_len: int,
                     seen_len: int = 0) -> bool:
        """Whether the state behind a prefill chunk that ends at
        ``chunk_end`` (``chunk`` tokens wide, whole pages) is worth a
        snapshot. Two boundaries of a prompt are: the DEEPEST shareable one
        (the next chunk's end lies past :meth:`_share_limit`: a later
        request that extends this prompt shares up to there), and
        ``seen_len``, the boundary up to which the prompt's pages were found
        cached at admission WITHOUT a snapshot to enter from
        (:meth:`match_prefix_state`): other requests demonstrably share that
        prefix, and this row is prefilling it anyway. (The deepest alone
        left a prefix whose first prompts all ran past it without a
        snapshot for good, and every later request prefilled it again:
        PERF.md, PR 42.) A shallower boundary serves only a request that
        diverges inside a prefix nobody has been seen to share, which is
        worth a slot only where slots are cheap: a model whose slot holds no
        more than one of its pages (a short convolution's tails: a twentieth
        of one; a recurrent matrix makes it two to eight) gets one behind EVERY chunk that ends
        on a shareable boundary, so a prefix has its snapshot from the first
        prompt that runs through it, whatever lies behind it."""
        limit = self._share_limit(prompt_len)
        return bool(self.snapshot_slots) and chunk_end <= limit and (
            self._snapshot_cheap or limit < chunk_end + chunk
            or chunk_end == seen_len)

    def alloc_snapshot(self) -> int:
        """A snapshot slot for a row about to copy its state: a free one,
        else the coldest cached one's (its entry stays, a shorter hit from
        now on), else 0: every slot is held by a row whose prefill is in
        flight, and the caller skips the snapshot."""
        if self._snapfree:
            sid = self._snapfree.pop()
        elif self._snaps:
            # the oldest that no row ever entered from, else the least
            # recently hit: a prompt's own deepest boundary (which nobody
            # shares) goes before a prefix that many do, and a NEW prefix's
            # snapshot outlives every older one that was never hit
            key = next((k for k in self._snaps if k not in self._snap_hit),
                       next(iter(self._snaps)))
            sid = self._forget_snapshot(key)
        else:
            return _NO_SNAPSHOT
        self.snapshots_taken += 1
        return sid

    def _forget_snapshot(self, key: bytes) -> int:
        """Take the snapshot off cache entry ``key`` (an eviction); its
        slot."""
        self._snap_hit.discard(key)
        self.snapshot_evictions += 1
        return self._snaps.pop(key)

    def snapshots_held(self) -> int:
        """Snapshots the cache's entries own (a hit can enter from)."""
        return len(self._snaps)

    def release_snapshots(self, ids) -> None:
        """Snapshot slots a row took and never published go back."""
        for sid in ids or ():
            assert sid not in self._snapfree, \
                f"snapshot slot {sid} released twice"
            self._snapfree.append(int(sid))

    def copy_page(self, src: int, dst: int) -> None:
        """Page ``src`` onto page ``dst`` in every array a page id indexes
        (and in no state array: those go by another id)."""
        if self._spec is not None:
            from ..models.hybrid import kv_page_copy

            self.pages = kv_page_copy(self.pages, src, dst, self._spec)
        else:
            from ..models.transformer import kv_page_copy

            self.pages = kv_page_copy(self.pages, src, dst)

    def copy_state(self, src: int, dst: int) -> None:
        """State slot ``src`` onto slot ``dst`` (rows' and snapshots' ids
        alike), in the stream."""
        from ..models.hybrid import state_slot_copy

        self.pages = state_slot_copy(self.pages, src, dst, self._spec)

    def release_row(self, group: "PagedGroup", slot: int) -> list[int]:
        """Free everything the row in ``slot`` of ``group`` holds, of every
        kind, and the slot itself: its window ring, its state slot, the
        snapshots it took and has not published, its pages. The one funnel
        of every retirement path. Returns the pages it held."""
        self.release_window(group.window_row_pages[slot])
        self.release_state(int(group.state_ids[slot]))
        self.release_snapshots((group.snapshots[slot] or {}).values())
        pages = group.release(slot)
        self.release(pages)
        return pages

    # ----------------------------------------------------- alloc / refcount

    def alloc(self, n: int) -> list[int]:
        """``n`` fresh pages (refcount 1 each), evicting cache-only pages
        LRU as needed. Raises :class:`PagePoolExhausted` when free +
        evictable < n — unreachable under the auto-sized pool (module
        docstring: allocation is row-bounded), guarded anyway."""
        while len(self._free) < n and self._evict_one():
            pass
        if len(self._free) < n:
            raise PagePoolExhausted(
                f"need {n} pages, {len(self._free)} free and nothing "
                f"evictable ({self.used_count()}/{self.capacity} used, "
                f"{self.cached_count()} cached)")
        out = [self._free.pop() for _ in range(n)]
        for p in out:
            self._ref[p] = 1
        return out

    def retain(self, pages) -> None:
        """One more referent per page (prefix-share acquisition)."""
        for p in pages:
            assert self._ref[p] > 0, f"retain of unowned page {p}"
            self._ref[p] += 1

    def release(self, pages) -> None:
        """Drop one referent per page; pages at zero return to the free
        list. Every retirement path funnels here exactly once per row
        (PagedGroup.release returns the row's distinct real pages)."""
        for p in pages:
            if p == 0:
                continue  # dummy padding in a table slice — never counted
            self._ref[p] -= 1
            assert self._ref[p] >= 0, f"page {p} released below zero"
            if self._ref[p] == 0:
                self._free.append(int(p))

    # -------------------------------------------------------- prefix cache

    @staticmethod
    def _page_key(prev: bytes, tokens: np.ndarray) -> bytes:
        """Rolling hash: page k's key digests (page k-1's key || page k's
        tokens), so one key identifies the whole prefix through page k."""
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.digest()

    def _share_limit(self, prompt_len: int) -> int:
        """Positions eligible for sharing: whole pages strictly before the
        prompt's last token — that token's page is always re-prefilled (its
        logits seed the first sample) and then written by decode, so it can
        never be a shared page."""
        return ((prompt_len - 1) // self.page_len) * self.page_len

    def match_prefix(self, prompt: np.ndarray) -> tuple[int, list[int]]:
        """Longest cached prefix of ``prompt`` in whole pages:
        ``(shared_len, pages)``, with one reference taken per matched page
        (the caller's row now co-owns them read-only). Counts a hit when
        at least one page matched, else a miss. (A model with state:
        :meth:`match_prefix_state`.)"""
        return self.match_prefix_state(prompt)[:2]

    def match_prefix_state(self, prompt: np.ndarray) \
            -> tuple[int, list[int], int, int]:
        """:meth:`match_prefix` and the snapshot to enter from:
        ``(shared_len, pages, snapshot slot, seen_len)``. For a model with
        state the match ends at the deepest cached boundary that HAS a
        snapshot (0 pages where none has), and the slot returned holds the
        state after ``shared_len`` tokens: the caller copies it into the
        row's slot before the row's first chunk (0 for a model without
        state). ``seen_len`` is how far the prompt's pages were found
        cached, snapshot or none (>= ``shared_len``): where it is deeper,
        the row's prefill is asked for a snapshot there
        (:meth:`snapshot_due`)."""
        if not self.prefix_cache_enabled:
            return 0, [], _NO_SNAPSHOT, 0
        prompt = np.asarray(prompt, np.int32)
        limit = self._share_limit(len(prompt))
        pages: list[int] = []
        keys: list[bytes] = []
        key = b""
        k = 0
        while (k + 1) * self.page_len <= limit:
            key = self._page_key(
                key, prompt[k * self.page_len:(k + 1) * self.page_len])
            e = self._cache.get(key)
            if e is None:
                break
            self._cache.move_to_end(key)  # LRU touch
            pages.append(e.page)
            keys.append(key)
            k += 1
        snap, seen_len = _NO_SNAPSHOT, len(keys) * self.page_len
        if self.snapshot_slots:
            deepest = max((i for i, k_ in enumerate(keys)
                           if k_ in self._snaps), default=-1)
            del pages[deepest + 1:]
            if pages:
                self._snaps.move_to_end(keys[deepest])
                self._snap_hit.add(keys[deepest])
                snap = self._snaps[keys[deepest]]
        if pages:
            self.retain(pages)
            self.hits += 1
        else:
            self.misses += 1
        return len(pages) * self.page_len, pages, snap, seen_len

    def insert_prefix(self, prompt: np.ndarray, row_pages,
                      snapshots: dict | None = None) -> int:
        """Cache the row's completed full prompt pages (called once, when
        the row's prefill finishes — the pages' contents are final from
        then on). ``row_pages`` is the row's block table in position order.
        Already-cached prefixes are skipped (no double reference); each
        newly cached page gains one cache-owned reference that outlives
        the row. ``snapshots`` (position -> snapshot slot, emptied here) are
        the snapshots the row took: each goes to the entry of the page that
        ends at its position (never hit yet: it goes before any that was,
        after every older one that was not), or back to the free list where
        that boundary has one already. Returns pages inserted."""
        if not self.prefix_cache_enabled:
            return 0  # (and no snapshot exists: they rest on the cache)
        snapshots = snapshots if snapshots is not None else {}
        prompt = np.asarray(prompt, np.int32)
        limit = self._share_limit(len(prompt))
        key = b""
        inserted = 0
        for k in range(limit // self.page_len):
            parent = key if k else None
            key = self._page_key(
                key, prompt[k * self.page_len:(k + 1) * self.page_len])
            e = self._cache.get(key)
            if e is not None:
                self._cache.move_to_end(key)
            else:
                page = int(row_pages[k])
                self._cache[key] = _CacheEntry(page, parent)
                if parent is not None:
                    self._cache[parent].children += 1
                self.retain([page])
                inserted += 1
            sid = snapshots.pop((k + 1) * self.page_len, None)
            if sid and key not in self._snaps:
                self._snaps[key] = sid
            elif sid:
                self.release_snapshots([sid])
        self.release_snapshots(snapshots.values())  # past the share limit
        snapshots.clear()
        return inserted

    def _evict_one(self) -> bool:
        """Evict the LRU cache entry that is a chain leaf (no cached
        children — evicting mid-chain would orphan unreachable deeper
        entries) and has no live readers (refcount is the cache's own).
        Returns False when nothing qualifies."""
        for key, e in self._cache.items():  # OrderedDict: oldest first
            if e.children == 0 and self._ref[e.page] == 1:
                del self._cache[key]
                if e.parent is not None:
                    self._cache[e.parent].children -= 1
                self.release([e.page])
                if key in self._snaps:  # its snapshot goes with it
                    self.release_snapshots([self._forget_snapshot(key)])
                self.evictions += 1
                return True
        return False

    # ------------------------------------------------------- copy-on-write

    def ensure_writable(self, table: np.ndarray, idx: int) -> bool:
        """Copy-on-write gate for one block-table slot: if the page has
        other referents (shared prefix, cache), allocate a fresh page,
        device-copy the contents (:func:`kv_page_copy` — ONE compiled
        program per slab shape), move this row's reference, and point the
        table at the copy. Returns True when a copy happened. The engine
        calls this before every page it is about to write; in steady state
        writes only ever target exclusively-owned pages (see
        :meth:`_share_limit`), so this is a cheap refcount check — but it
        is the contract that makes sharing safe against any future
        scheduler change, and the unit tests drive it directly."""
        page = int(table[idx])
        if page == 0 or self._ref[page] <= 1:
            return False
        fresh = self.alloc(1)[0]
        self.copy_page(page, fresh)
        self.release([page])
        table[idx] = fresh
        self.cow_copies += 1
        return True

    # ------------------------------------------------- cross-pool migration

    def _layer_names(self) -> list[str]:
        return sorted(self.pages, key=lambda s: int(s[1:]))

    def _host_pages(self) -> dict:
        """One whole-slab device→host fetch (migration is a restart-path
        operation; a per-row device gather would compile one program per
        row-set size and break the bounded-compiles guarantee)."""
        return {name: [np.array(t) for t in self.pages[name]]
                for name in self._layer_names()}

    def _flush_host(self, host) -> None:
        """Push a host slab copy back to the device wholesale."""
        if host is None:
            return
        import jax.numpy as jnp

        self.pages = {name: tuple(jnp.asarray(a) for a in kv)
                      for name, kv in host.items()}

    def _geometry(self) -> dict:
        names = self._layer_names()
        leaf = self.pages[names[0]][0]
        return {"page_len": self.page_len, "layers": names,
                "dtype": str(np.dtype(leaf.dtype)),
                "shapes": [list(np.shape(self.pages[nm][0])[1:])
                           for nm in names]}

    def _check_geometry(self, meta: dict) -> None:
        geo = self._geometry()
        for field in ("page_len", "layers", "dtype", "shapes"):
            if meta.get(field) != geo[field]:
                raise MigrationCorruptError(
                    f"pool geometry mismatch on {field!r}: blob has "
                    f"{meta.get(field)!r}, target pool has {geo[field]!r}")

    def _row_nbytes(self, n_pages: int) -> int:
        geo = self._geometry()
        item = np.dtype(geo["dtype"]).itemsize
        # shapes[i] is one page of layer i's arrays ((page_len, kvh, dh) for
        # K and for V, (page_len, kvh * dh) in a spec model's slab;
        # (page_len, entry) for a latent layer's one array)
        per_page = sum(int(np.prod(shape)) * len(self.pages[name])
                       for name, shape in zip(geo["layers"], geo["shapes"]))
        return n_pages * per_page * item

    def export_rows(self, rows) -> bytes:
        """Serialize a row set into a CRC-framed host blob. Each element of
        ``rows`` is a dict carrying the row's block table in position order
        (``pages``), its prompt/cursor/sampling manifest (engine-provided;
        travels verbatim in the meta chunk), and ``rid``. Page contents are
        gathered device→host once for the whole set. The blob is
        self-contained: :meth:`import_rows` on any pool with matching
        geometry rebuilds the rows without reference to this pool."""
        self._refuse_private("row migration (export_rows)")
        host = self._host_pages()
        names = self._layer_names()
        meta = {"version": 1, "kind": "rows", **self._geometry(),
                "rows": [dict(r, pages=[int(p) for p in r["pages"]])
                         for r in rows]}
        blob = [_mig_frame(
            _MIG_META, json.dumps(meta, default=_mig_default).encode())]
        for r in meta["rows"]:
            pids = np.asarray(r["pages"], np.int64)
            body = b"".join(
                np.ascontiguousarray(arr[pids]).tobytes()
                for name in names for arr in host[name])
            blob.append(_mig_frame(_MIG_ROW, body))
        return b"".join(blob)

    def import_rows(self, blob: bytes) -> list[dict]:
        """Rebuild an exported row set in THIS pool: validate every chunk
        (corruption always raises :class:`MigrationCorruptError`), then per
        row run the NORMAL allocation path — :meth:`match_prefix` first, so
        a migrated shared prefix re-deduplicates against the target's cache
        (and against earlier rows of this same blob, whose completed prompt
        pages are re-inserted as they land), then :meth:`alloc` for the
        remainder — and scatter the imported page contents into the slab.
        Returns the row manifests with target-space ``pages``/``n_shared``/
        ``shared_len`` rebound; the caller binds them to entries. On any
        failure every page this call allocated is released (pages already
        content-written stay valid for the cache entries that reference
        them), so a failed import leaks nothing."""
        self._refuse_private("row migration (import_rows)")
        chunks = _mig_chunks(blob)
        if not chunks or chunks[0][0] != _MIG_META:
            raise MigrationCorruptError("blob does not start with a meta "
                                        "chunk")
        try:
            meta = json.loads(chunks[0][1].decode())
        except ValueError as exc:
            raise MigrationCorruptError(f"meta chunk not JSON: {exc}")
        if meta.get("version") != 1 or meta.get("kind") != "rows":
            raise MigrationCorruptError(
                f"unsupported blob version/kind: {meta.get('version')}/"
                f"{meta.get('kind')}")
        self._check_geometry(meta)
        bodies = [b for kind, b in chunks[1:] if kind == _MIG_ROW]
        if len(bodies) != len(meta["rows"]):
            raise MigrationCorruptError(
                f"row count mismatch: meta lists {len(meta['rows'])} rows, "
                f"blob carries {len(bodies)} page chunks")
        for row, body in zip(meta["rows"], bodies):
            if len(body) != self._row_nbytes(len(row["pages"])):
                raise MigrationCorruptError(
                    f"row {row.get('rid')}: page payload is {len(body)} "
                    f"bytes, expected "
                    f"{self._row_nbytes(len(row['pages']))}")
        names = self._layer_names()
        dtype = np.dtype(meta["dtype"])
        out: list[dict] = []
        taken: list[list[int]] = []
        host = None
        try:
            for row, body in zip(meta["rows"], bodies):
                prompt = np.asarray(row["prompt"], np.int32)
                n_pages = len(row["pages"])
                shared_len, spages = self.match_prefix(prompt)
                owned = self.alloc(n_pages - len(spages))
                pages = list(spages) + owned
                taken.append(pages)
                if owned:
                    if host is None:
                        host = self._host_pages()
                    off = 0
                    for name, shape in zip(names, meta["shapes"]):
                        cnt = n_pages * int(np.prod(shape))
                        nb = cnt * dtype.itemsize
                        for slab in host[name]:
                            arr = np.frombuffer(
                                body, dtype, cnt, off).reshape(
                                    [n_pages] + shape)
                            slab[owned] = arr[len(spages):]
                            off += nb
                row = dict(row, pages=pages, n_shared=len(spages),
                           shared_len=shared_len)
                out.append(row)
                if int(row.get("pf_next", -1)) < 0:
                    # prefill completed on the source: publish the prompt's
                    # full pages so later arrivals — including later rows
                    # of this same blob — share instead of re-importing
                    self.insert_prefix(prompt, pages)
        except BaseException:
            # pages already written hold valid content — flush them so any
            # cache entry inserted above stays safe, then drop row refs
            self._flush_host(host)
            for pages in taken:
                self.release(pages)
            raise
        self._flush_host(host)
        return out

    def export_prefixes(self, n: int) -> bytes | None:
        """The N hottest prefix-cache entries (MRU end of the LRU order),
        closed over their parent chains (a child without its ancestors can
        never be matched), as a CRC-framed blob for warming a peer's cache.
        Keys are the content hashes themselves — no prompt tokens travel.
        Returns None when there is nothing to export."""
        self._refuse_private("prefix export")
        if not self.prefix_cache_enabled or not self._cache:
            return None
        selected: set[bytes] = set()
        for key in list(self._cache)[-max(1, int(n)):]:
            while key is not None and key not in selected:
                selected.add(key)
                key = self._cache[key].parent

        def depth(k: bytes) -> int:
            d = 0
            e = self._cache[k]
            while e.parent is not None:
                d += 1
                e = self._cache[e.parent]
            return d

        ordered = sorted(selected, key=depth)  # parents import first
        host = self._host_pages()
        names = self._layer_names()
        entries = []
        body = []
        for key in ordered:
            e = self._cache[key]
            entries.append({
                "key": key.hex(),
                "parent": None if e.parent is None else e.parent.hex()})
            pid = np.asarray([e.page], np.int64)
            body.append(b"".join(
                np.ascontiguousarray(arr[pid]).tobytes()
                for name in names for arr in host[name]))
        meta = {"version": 1, "kind": "prefixes", **self._geometry(),
                "entries": entries}
        return (_mig_frame(_MIG_META, json.dumps(meta).encode())
                + _mig_frame(_MIG_PREFIX, b"".join(body)))

    def import_prefixes(self, blob: bytes) -> int:
        """Warm this pool's prefix cache from a peer's
        :meth:`export_prefixes` blob: each entry allocates one page (LRU
        eviction may make room; exhaustion stops the warm early rather than
        failing it), takes the cache-owned reference, and links into the
        parent chain. Entries already cached (or whose parent did not make
        the cut) are skipped. Returns entries inserted."""
        self._refuse_private("prefix import")
        if not self.prefix_cache_enabled:
            return 0
        chunks = _mig_chunks(blob)
        if not chunks or chunks[0][0] != _MIG_META:
            raise MigrationCorruptError("blob does not start with a meta "
                                        "chunk")
        try:
            meta = json.loads(chunks[0][1].decode())
        except ValueError as exc:
            raise MigrationCorruptError(f"meta chunk not JSON: {exc}")
        if meta.get("version") != 1 or meta.get("kind") != "prefixes":
            raise MigrationCorruptError(
                f"unsupported blob version/kind: {meta.get('version')}/"
                f"{meta.get('kind')}")
        self._check_geometry(meta)
        bodies = [b for kind, b in chunks[1:] if kind == _MIG_PREFIX]
        body = bodies[0] if bodies else b""
        per_entry = self._row_nbytes(1)
        if len(body) != per_entry * len(meta["entries"]):
            raise MigrationCorruptError(
                f"prefix payload is {len(body)} bytes, expected "
                f"{per_entry * len(meta['entries'])}")
        names = self._layer_names()
        dtype = np.dtype(meta["dtype"])
        host = None
        inserted = 0
        for i, ent in enumerate(meta["entries"]):
            key = bytes.fromhex(ent["key"])
            parent = None if ent["parent"] is None \
                else bytes.fromhex(ent["parent"])
            if key in self._cache:
                self._cache.move_to_end(key)
                continue
            if parent is not None and parent not in self._cache:
                continue  # chain broken (parent evicted/skipped)
            try:
                page = self.alloc(1)[0]
            except PagePoolExhausted:
                break  # a partial warm is still a warm
            if host is None:
                host = self._host_pages()
            off = i * per_entry
            for name, shape in zip(names, meta["shapes"]):
                cnt = int(np.prod(shape))
                nb = cnt * dtype.itemsize
                for slab in host[name]:
                    slab[page] = np.frombuffer(
                        body, dtype, cnt, off).reshape(shape)
                    off += nb
            self._cache[key] = _CacheEntry(page, parent)
            if parent is not None:
                self._cache[parent].children += 1
            inserted += 1
        self._flush_host(host)
        return inserted

    # --------------------------------------------------------------- audit

    def audit(self, groups=()) -> dict:
        """Cross-check every pool invariant: refcounts vs block-table
        references vs the free list vs prefix-cache ownership, the pinned
        dummy page, and cache parent/children chain consistency. ``groups``
        is the engine's live :class:`PagedGroup` set — row-side references
        are only checkable when the caller passes them (chaos tests and
        ``GET /debug/kvpool`` do). Returns ``{"ok": bool, "errors": [...],
        **stats}``; read-only, never raises."""
        errors: list[str] = []
        expect = np.zeros(self.num_pages, np.int64)
        expect[0] = 1  # the dummy pin
        errors += self._audit_window(groups)
        errors += self._audit_state(groups)
        for g in groups:
            for slot in g.occupied_slots():
                for p in (g.row_pages[slot] or []):
                    p = int(p)
                    if not 0 < p < self.num_pages:
                        errors.append(f"row table references out-of-range "
                                      f"page {p}")
                        continue
                    expect[p] += 1
        children: dict[bytes, int] = {}
        for key, e in self._cache.items():
            if not 0 < e.page < self.num_pages:
                errors.append(f"cache entry references out-of-range page "
                              f"{e.page}")
                continue
            expect[e.page] += 1
            if e.parent is not None:
                if e.parent not in self._cache:
                    errors.append(f"cache entry for page {e.page} orphaned: "
                                  f"parent key missing")
                else:
                    children[e.parent] = children.get(e.parent, 0) + 1
        for key, e in self._cache.items():
            want = children.get(key, 0)
            if e.children != want:
                errors.append(f"cache entry for page {e.page}: children "
                              f"count {e.children} != {want} actual")
        free = [int(p) for p in self._free]
        fs = set(free)
        if len(fs) != len(free):
            errors.append("free list contains duplicate pages")
        if 0 in fs:
            errors.append("dummy page 0 is on the free list")
        if int(self._ref[0]) < 1:
            errors.append(f"dummy page 0 unpinned (refcount "
                          f"{int(self._ref[0])})")
        for p in fs:
            if not 0 < p < self.num_pages:
                errors.append(f"free list holds out-of-range page {p}")
            elif int(self._ref[p]) != 0:
                errors.append(f"free page {p} has refcount "
                              f"{int(self._ref[p])}")
            if int(expect[p]) != 0 and 0 < p < self.num_pages:
                errors.append(f"free page {p} is still referenced by a row "
                              f"or cache entry")
        for p in range(1, self.num_pages):
            ref = int(self._ref[p])
            if p in fs:
                continue
            if ref == 0:
                errors.append(f"page {p} leaked: refcount 0 but not on the "
                              f"free list")
            elif groups and ref != int(expect[p]):
                errors.append(f"page {p}: refcount {ref} != "
                              f"{int(expect[p])} referents")
            elif not groups and ref < int(expect[p]):
                errors.append(f"page {p}: refcount {ref} below its "
                              f"{int(expect[p])} cache references")
        return {"ok": not errors, "errors": errors, **self.stats()}


    def _audit_window(self, groups) -> list[str]:
        """The window class's half of :meth:`audit`: every ring within its
        bound, every page either free or held by exactly one row."""
        if not self.window_pages:
            return []
        errors: list[str] = []
        expect = np.zeros(self.window_pages, np.int64)
        expect[0] = 1
        for g in groups:
            for slot in g.occupied_slots():
                ring = g.window_row_pages[slot] or []
                if len(ring) > self.ring:
                    errors.append(f"a row's window table holds {len(ring)} "
                                  f"pages, over its bound {self.ring}")
                for p in ring:
                    if not 0 < int(p) < self.window_pages:
                        errors.append(f"window table references "
                                      f"out-of-range page {int(p)}")
                    else:
                        expect[int(p)] += 1
        fs = set(self._wfree)
        if len(fs) != len(self._wfree):
            errors.append("window free list contains duplicate pages")
        if 0 in fs or int(self._wref[0]) < 1:
            errors.append("window dummy page 0 is free or unpinned")
        for p in range(1, self.window_pages):
            ref = int(self._wref[p])
            if p in fs:
                if ref or expect[p]:
                    errors.append(f"free window page {p} is still referenced")
            elif ref == 0:
                errors.append(f"window page {p} leaked: refcount 0 but not "
                              f"on the free list")
            elif groups and ref != int(expect[p]):
                errors.append(f"window page {p}: refcount {ref} != "
                              f"{int(expect[p])} referents")
        return errors


    def _audit_state(self, groups) -> list[str]:
        """The state slots' half of :meth:`audit`: every slot either free or
        held by exactly one resident row, every resident row holding one;
        every snapshot slot free, owned by one cache entry that exists, or
        held by one resident row that has yet to publish it."""
        if not self.state_slots:
            return []
        errors: list[str] = self._audit_snapshots(groups)
        held = np.zeros(self.state_slots, np.int64)
        for g in groups:
            for slot in g.occupied_slots():
                sid = int(g.state_ids[slot])
                if not 0 < sid < self.state_slots:
                    errors.append(f"a resident row holds state slot {sid}, "
                                  f"not one of 1..{self.state_slots - 1}")
                else:
                    held[sid] += 1
        fs = set(self._sfree)
        if len(fs) != len(self._sfree):
            errors.append("state free list contains duplicate slots")
        if 0 in fs:
            errors.append("dummy state slot 0 is on the free list")
        for sid in range(1, self.state_slots):
            if sid in fs:
                if held[sid]:
                    errors.append(f"free state slot {sid} is still held by "
                                  f"a row")
            elif groups and held[sid] != 1:
                errors.append(f"state slot {sid}: off the free list with "
                              f"{int(held[sid])} rows holding it")
        return errors


    def _audit_snapshots(self, groups) -> list[str]:
        errors: list[str] = []
        lo, hi = self.state_slots, self.state_slots + self.snapshot_slots
        owners = np.zeros(hi, np.int64)
        for key, sid in self._snaps.items():
            if key not in self._cache:
                errors.append(f"snapshot slot {sid} belongs to a cache "
                              f"entry that is gone")
            if lo <= sid < hi:
                owners[sid] += 1
            else:
                errors.append(f"the cache holds snapshot slot {sid}, not "
                              f"one of {lo}..{hi - 1}")
        for g in groups:
            for slot in g.occupied_slots():
                for sid in (g.snapshots[slot] or {}).values():
                    if lo <= sid < hi:
                        owners[sid] += 1
                    else:
                        errors.append(f"a row holds snapshot slot {sid}, "
                                      f"not one of {lo}..{hi - 1}")
        fs = set(self._snapfree)
        if len(fs) != len(self._snapfree):
            errors.append("snapshot free list contains duplicate slots")
        for sid in range(lo, hi):
            if sid in fs and owners[sid]:
                errors.append(f"free snapshot slot {sid} is still owned")
            elif sid not in fs and owners[sid] > 1:
                errors.append(f"snapshot slot {sid} has {owners[sid]} "
                              f"owners")
            elif sid not in fs and groups and not owners[sid]:
                errors.append(f"snapshot slot {sid} leaked: off the free "
                              f"list and owned by nothing")
        return errors


class PagedGroup:
    """Per-bucket row bookkeeping over a shared :class:`PagedKVPool`. Owns
    the per-row vectors the decode program takes (:func:`decode_inputs`
    gathers the live rows of every bucket's group), each row's block table
    and prefill cursor, and the host-side emitted-token stream (results are
    assembled host-side from the tokens that have LANDED; the engine
    dispatches a row's next step before its last token has, so the
    cursors here run ahead of ``emitted`` by what is in flight, and
    ``fed_serial``/``fed_index`` say where on the device the row's current
    token lies meanwhile). Single-threaded — only the engine worker touches
    a group."""

    def __init__(self, bucket, width: int, page_len: int,
                 prefill_chunk: int, ring: int | None = None,
                 stateful: bool = False):
        p, s = bucket
        self.bucket = bucket
        self.width = width
        self.page_len = page_len
        #: block-table width for DECODE: pages covering the bucket extent
        self.pages_per_row = -(-(p + s) // page_len)
        #: compiled chunk width in tokens (:func:`group_chunk`)
        self.chunk = group_chunk(bucket, page_len, prefill_chunk)
        self.chunk_pages = self.chunk // page_len
        #: pages of a row's window ring: the row's whole window table, for
        #: prefill and decode alike. None: the dense block's programs, which
        #: take one table; a ModelSpec's take ``(table, ring)``, the ring 0
        #: wide where no layer slides
        self.ring = ring
        self.window_tables = np.zeros((width, ring or 0), np.int32)
        self.window_row_pages: list = [None] * width
        #: whether the programs take a state slot a row (a model with
        #: state-space mixers), and each row's: an id of the POOL's
        #: (:meth:`PagedKVPool.alloc_state`; 0: none), not the row's slot
        #: in this group
        self.stateful = stateful
        self.state_ids = np.zeros(width, np.int32)
        #: the snapshots a row took and has yet to publish with its pages:
        #: position -> snapshot slot of the pool (None: a free slot); and
        #: the boundary up to which the row's prompt was found cached at
        #: admission with no snapshot to enter from (0: none such)
        self.snapshots: list = [None] * width
        self.seen_len = np.zeros(width, np.int64)
        #: stored table width: decode extent + chunk spill (a final chunk
        #: starting near the extent scatters into these dummy-page slots)
        self.table_width = self.pages_per_row + self.chunk_pages
        self.tables = np.zeros((width, self.table_width), np.int32)
        self.entries: list = [None] * width
        self.positions = np.zeros(width, np.int32)
        self.steps_done = np.zeros(width, np.int32)
        self.lengths = np.zeros(width, np.int32)
        self.seeds = np.zeros(width, np.uint32)
        self.temperature = np.zeros(width, np.float32)
        self.top_p = np.ones(width, np.float32)   # 1.0 = nucleus filter off
        self.top_k = np.zeros(width, np.int32)    # 0 = rank filter off
        self.cur_tok = np.zeros(width, np.int32)
        #: where the row's current token lies while the host has not seen
        #: it: entry ``fed_index`` of the engine's device feed number
        #: ``fed_serial`` (0: nowhere; ``cur_tok`` holds it once landed)
        self.fed_serial = np.zeros(width, np.int64)
        self.fed_index = np.zeros(width, np.int32)
        self.ttft_s: list = [None] * width
        #: next chunk_start per row; -1 = not prefilling (free or decoding)
        self.pf_next = np.full(width, -1, np.int64)
        self.prompts: list = [None] * width   # chunk-padded prompt arrays
        self.emitted: list = [None] * width   # host-side generated tokens
        self.row_pages: list = [None] * width  # table pages, position order
        self.shared_pages = np.zeros(width, np.int32)

    # --------------------------------------------------------------- state

    def occupied_slots(self) -> list[int]:
        return [i for i, e in enumerate(self.entries) if e is not None]

    def live_slots(self) -> list[int]:
        """Decode-ready rows (prefill complete)."""
        return [i for i, e in enumerate(self.entries)
                if e is not None and self.pf_next[i] < 0]

    def prefilling_slots(self) -> list[int]:
        return [i for i, e in enumerate(self.entries)
                if e is not None and self.pf_next[i] >= 0]

    def free_slots(self) -> list[int]:
        return [i for i, e in enumerate(self.entries) if e is None]

    def occupancy(self) -> float:
        return len(self.live_slots()) / self.width

    # ---------------------------------------------------------- transitions

    def assign(self, slot: int, entry, pages: list[int], shared_len: int,
               n_shared: int, window_pages=(), state_id: int = 0) -> None:
        """Bind an admitted entry: ``pages`` is the row's full block table
        in position order (``n_shared`` prefix-cache pages first, then the
        freshly allocated remainder); prefill resumes at ``shared_len``.
        ``window_pages`` is the row's ring of the window class (at most
        ``ring``; a short request holds only the slots it can reach),
        ``state_id`` its state slot in the pool."""
        r = entry.request
        n = r.prompt.shape[0]
        self.entries[slot] = entry
        self.lengths[slot] = n
        self.tables[slot, :] = 0
        self.tables[slot, :len(pages)] = pages
        self.row_pages[slot] = list(pages)
        self.window_tables[slot, :] = 0
        self.window_tables[slot, :len(window_pages)] = window_pages
        self.window_row_pages[slot] = list(window_pages)
        self.state_ids[slot] = state_id
        self.snapshots[slot] = {}
        self.shared_pages[slot] = n_shared
        self.pf_next[slot] = shared_len
        padded = np.zeros(_round_up(n, self.chunk), np.int32)
        padded[:n] = r.prompt
        self.prompts[slot] = padded
        self.positions[slot] = 0
        self.steps_done[slot] = 0
        self.cur_tok[slot] = 0
        self.fed_serial[slot] = 0
        self.seeds[slot] = np.uint32(r.seed)
        self.temperature[slot] = r.temperature
        self.top_p[slot] = 1.0 if r.top_p is None else r.top_p
        self.top_k[slot] = 0 if r.top_k is None else r.top_k
        self.emitted[slot] = []
        self.ttft_s[slot] = None

    def begin_decode(self, slot: int) -> None:
        """The final chunk is DISPATCHED: the row is decode-ready, its first
        token still on the device (:meth:`land_first` brings it)."""
        self.pf_next[slot] = -1
        self.positions[slot] = self.lengths[slot]
        self.steps_done[slot] = 1

    def land_first(self, slot: int, first: int) -> None:
        """The final chunk's token reached the host."""
        self.cur_tok[slot] = first
        self.emitted[slot] = [int(first)]

    def restore(self, slot: int, entry, row: dict,
                pages: list[int]) -> None:
        """Bind a MIGRATED row mid-stream (:meth:`PagedKVPool.import_rows`
        manifest): like :meth:`assign` but restoring the source replica's
        cursors — position, steps_done, current token, emitted stream, and
        the prefill cursor for rows frozen mid-prefill. With the imported
        KV pages in place, decode resumes bit-identically: the sampling
        stream is ``fold_in(key(seed), step)``, composition-independent,
        so only (seed, steps_done, KV, cur_tok) matter — all restored."""
        r = entry.request
        n = int(row["length"])
        self.entries[slot] = entry
        self.lengths[slot] = n
        self.tables[slot, :] = 0
        self.tables[slot, :len(pages)] = pages
        self.row_pages[slot] = list(pages)
        self.shared_pages[slot] = int(row["n_shared"])
        self.pf_next[slot] = int(row["pf_next"])
        padded = np.zeros(_round_up(n, self.chunk), np.int32)
        padded[:n] = r.prompt
        self.prompts[slot] = padded
        self.positions[slot] = int(row["position"])
        self.steps_done[slot] = int(row["steps_done"])
        self.cur_tok[slot] = int(row["cur_tok"])
        self.fed_serial[slot] = 0
        self.seeds[slot] = np.uint32(r.seed)
        self.temperature[slot] = r.temperature
        self.top_p[slot] = 1.0 if r.top_p is None else r.top_p
        self.top_k[slot] = 0 if r.top_k is None else r.top_k
        self.emitted[slot] = [int(t) for t in row["emitted"]]
        self.ttft_s[slot] = row.get("ttft_s")

    def release(self, slot: int) -> list[int]:
        """Free the slot on ANY retirement path; returns the row's pages
        for the caller to hand to :meth:`PagedKVPool.release` — the single
        page-release funnel per row."""
        pages = self.row_pages[slot] or []
        self.entries[slot] = None
        self.tables[slot, :] = 0
        self.row_pages[slot] = None
        self.window_tables[slot, :] = 0
        self.window_row_pages[slot] = None
        self.state_ids[slot] = 0
        self.snapshots[slot] = None
        self.shared_pages[slot] = 0
        self.pf_next[slot] = -1
        self.positions[slot] = 0
        self.steps_done[slot] = 0
        self.lengths[slot] = 0
        self.cur_tok[slot] = 0
        self.fed_serial[slot] = 0
        self.temperature[slot] = 0.0
        self.top_p[slot] = 1.0
        self.top_k[slot] = 0
        self.prompts[slot] = None
        self.emitted[slot] = None
        self.ttft_s[slot] = None
        return pages

    def prefill_tables(self, slot: int):
        """What the prefill program takes for ``slot``: the row's table,
        its window ring beside it where the model is a spec, and its state
        slot after them where the model has state-space mixers."""
        if self.stateful:
            return (self.tables[slot], self.window_tables[slot],
                    self.state_ids[slot])
        if self.ring is not None:
            return self.tables[slot], self.window_tables[slot]
        return self.tables[slot]


def decode_pages(buckets, page_len: int) -> int:
    """The table width of an engine's one decode program: the pages that
    cover the WIDEST bucket's extent. Rows of a narrower bucket ride the
    same call, their tables ending in the dummy page (the decode kernel
    neither fetches nor computes a page past a row's length)."""
    return max(-(-(p + s) // page_len) for p, s in buckets)


def decode_inputs(rows, width: int, pages_per_row: int,
                  ring: int | None = None, serial: int = 0,
                  stateful: bool = False):
    """What one call of the decode program takes after the slab, for the
    LIVE rows ``rows`` — ``(group, slots)`` runs in call order, of any
    buckets' groups, at most ``width`` rows in all, packed from row 0:
    ``(tables, positions, cur_tokens, steps_done, seeds, temperature, top_p,
    top_k, prev_index)``, each ``width`` rows. ``prev_index`` is, for a row
    whose current token lies in device feed number ``serial`` (the engine's
    present one), its entry there; -1 for every other row, whose token is
    the host's ``cur_tokens``. A row's block table is laid into
    ``pages_per_row`` columns (:func:`decode_pages`); what lies past its own
    bucket's extent is the dummy page 0. The rows no live row fills are the
    dummy row: table and position 0 — a prefilling row's REAL pages must
    never be scribbled by a dummy decode write, so such a row is simply not
    handed in — and temperature 0: the decode program sorts the vocabulary
    for the whole call when any row it is handed samples. With ``ring`` (a
    ModelSpec's programs take both classes of page) ``tables`` is
    ``(tables, window rings)``, and with ``stateful`` ``(tables, window
    rings, state slots)``: a row's place in the call is not its state slot
    (the call packs its live rows from row 0 anew every iteration), so each
    row's pool-level slot id rides beside its table, and the rows no live
    row fills name the dummy state slot 0 as they name the dummy page."""
    tables = np.zeros((width, pages_per_row), np.int32)
    rings = np.zeros((width, ring or 0), np.int32)
    state_ids = np.zeros(width, np.int32)
    positions = np.zeros(width, np.int32)
    cur = np.zeros(width, np.int32)
    steps_done = np.zeros(width, np.int32)
    seeds = np.zeros(width, np.uint32)
    temperature = np.zeros(width, np.float32)
    top_p = np.ones(width, np.float32)
    top_k = np.zeros(width, np.int32)
    prev_index = np.full(width, -1, np.int32)
    at = 0
    for g, slots in rows:
        to = slice(at, at + len(slots))
        at += len(slots)
        tables[to, :g.pages_per_row] = g.tables[slots, :g.pages_per_row]
        rings[to] = g.window_tables[slots]
        state_ids[to] = g.state_ids[slots]
        positions[to] = g.positions[slots]
        cur[to] = g.cur_tok[slots]
        steps_done[to] = g.steps_done[slots]
        seeds[to] = g.seeds[slots]
        temperature[to] = g.temperature[slots]
        top_p[to] = g.top_p[slots]
        top_k[to] = g.top_k[slots]
        prev_index[to] = np.where(g.fed_serial[slots] == serial,
                                  g.fed_index[slots], -1)
    if stateful:
        tables = (tables, rings, state_ids)
    elif ring is not None:
        tables = (tables, rings)
    return (tables, positions, cur, steps_done, seeds, temperature, top_p,
            top_k, prev_index)


# ---------------------------------------------------------------- programs


def warmup_paged(params: dict, heads: int, buckets, max_batch: int,
                 pool: PagedKVPool, prefill_chunk: int,
                 compute_dtype: str | None = None,
                 moe: tuple | None = None, kernel: str = "gather") -> int:
    """Compile (and execute once, against dummy page 0) every bucket's
    chunked-prefill program, the ONE decode program every bucket's rows ride
    (``max_batch`` rows, the widest bucket's table: :func:`decode_pages`),
    the token feed's write (:func:`~marlin_tpu.models.transformer
    .feed_token`) and the one shared page-copy program — a program per
    bucket and three, the whole paged compile story. The decode program runs
    twice, fed as the engine feeds it: first from a fresh feed, then from its
    own tokens. Runs against the engine's REAL pool
    (program identity includes the slab shape, so a throwaway pool would
    compile programs traffic never hits); all dummy writes land in page 0.
    Returns the buckets warmed."""
    import jax

    from ..models.transformer import (feed_token, lm_decode_paged,
                                      lm_prefill_paged)
    from .batcher import normalize_buckets

    buckets = normalize_buckets(buckets)
    ring = None if isinstance(heads, int) else pool.ring
    stateful = bool(pool.state_slots)
    groups = [PagedGroup(bucket, max_batch, pool.page_len, prefill_chunk,
                         ring=ring, stateful=stateful) for bucket in buckets]
    table_pages = decode_pages(buckets, pool.page_len)
    *dummy, unfed = decode_inputs((), max_batch, table_pages, ring,
                                  stateful=stateful)
    feed = np.zeros(max_batch, np.int32)
    if not isinstance(heads, int):
        # a spec's programs take the compiler half a minute each and leave
        # most cores idle: all of them at once, then the calls below run
        # what is compiled
        from ..models import hybrid
        from ..models.transformer import resolve_decode_kernel

        hybrid.precompile_paged(
            [(params, pool.pages, g.prefill_tables(0),
              np.zeros(g.chunk, np.int32), 0, 1, heads, pool.page_len)
             for g in groups],
            [(params, pool.pages, *dummy, heads, pool.page_len,
              resolve_decode_kernel(kernel), feed, unfed)])
    for g in groups:
        pool.pages, first = lm_prefill_paged(
            params, pool.pages, g.prefill_tables(0),
            np.zeros(g.chunk, np.int32), 0, 1, heads=heads,
            page_len=pool.page_len, compute_dtype=compute_dtype,
            moe=moe)[:2]
    for _ in range(2):
        pool.pages, feed = lm_decode_paged(
            params, pool.pages, *dummy, heads=heads, page_len=pool.page_len,
            compute_dtype=compute_dtype, moe=moe, kernel=kernel,
            prev_tokens=feed_token(feed, 0, first), prev_index=unfed)[:2]
    jax.block_until_ready(feed)
    if pool.snapshot_slots:
        pool.copy_state(0, 0)  # taking a snapshot and entering from one
    pool.copy_page(0, 0)  # the last program
    jax.block_until_ready(pool.pages["l0"][0])
    return len(buckets)
