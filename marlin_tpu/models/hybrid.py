"""A decoder described by a spec built from a published configuration.

``transformer.py`` has one block, fixed by ``heads: int``: head size tied to
the width, no positional term, a two-matrix GELU FFN, a tied head. The models
people serve mix layer kinds, so this module describes a model by a frozen,
hashable :class:`ModelSpec` (static under ``jit``) read from a Hugging Face
style configuration dict (:meth:`ModelSpec.from_config`):

- a head size independent of the width, and query heads **per layer**;
- layers of three attention kinds, ``full`` (every earlier position),
  ``sliding`` (the last ``window`` positions: key ``j`` is visible to query
  ``i`` iff ``0 <= i - j < window``), each with its own rotary embedding
  (plain, or YaRN on part of each head), and ``latent`` (below; with an
  indexer, over the TOKENS it selects);
- a fourth kind of layer (``LayerSpec.ssm``; below): full attention AND a
  state-space mixer side by side on one normed input, their outputs added;
- a fifth, ``linear`` (below): a gated delta-rule mixer and NO attention,
  a layer that owns no page at all;
- a sixth, ``conv`` (below): a gated SHORT CONVOLUTION and no attention,
  a layer that owns no page and no recurrent matrix either;
- a seventh, ``sparse`` (below): attention over a SELECTION of the row's
  blocks, chosen by a score over mean-pooled keys kept beside the pages;
- an eighth, ``lightning`` (below): linear attention with a constant decay
  a head and NO attention, a layer that owns no page;
- a ninth, ``kda`` (below): a gated delta rule whose decay is a VECTOR a
  head, in a model whose every layer is an expert layer;
- a per-head sigmoid gate on the attention output, before ``wo``;
- a SwiGLU FFN, dense or a mixture of experts with a shared expert
  (:func:`~marlin_tpu.models.moe.moe_experts_ffn`: the layer is told which
  experts it holds and computes their part of the result), or with none;
- an untied head over the rows of the vocabulary that are held here, or
  (``tied_head``) the embedding itself as the head: no second table;
- parameters in ``param_dtype`` (norm gains and the router in float32);
  matmul operands and the KV cache in ``compute_dtype``; the residual
  stream, the norms, the router, the rotary embedding, the gate and every
  softmax in float32 (a bfloat16 residual stream would round the whole
  stream at every layer; the float32 one costs nothing measurable on the
  chip, ``PERF.md`` section 6, PR 31).

The block's arithmetic exists once (:func:`layer_forward`); what differs
between the two paged programs is only how a query meets the cache, handed in
as ``attend`` (and how a mixer meets its state, handed in as ``mix``). Only
the paged serving path runs such a model
(``ServeEngine(params, spec)`` -> ``lm_prefill_paged`` / ``lm_decode_paged``,
which hand a spec to :func:`prefill_paged` / :func:`decode_paged` here):
``lm_generate`` and the trainer raise for a spec
(:func:`require_int_heads`).

Two classes of KV page (``serving/kvpool.py``): a full layer's slab is
indexed by the row's *global* table (every position); a sliding layer's slab
by the row's *window* table, a ring of :func:`window_ring_pages` pages in
which position ``p`` lives in slot ``(p // page_len) % ring``.

**A state slot beside the pages** (:class:`SsmSpec`, :class:`Multipliers`;
the ``falcon_h1`` configuration family's keys). A layer with a state-space
(Mamba-2) mixer keeps two memories of the past: its attention's keys and
values in pages of the global class, as any full layer, and its mixer's
**recurrent state**, per row ``(heads, state, head_dim)`` float32, with the
**tail** of the mixer's causal convolution (its last ``conv - 1`` inputs).
State and tail are fixed in size, neither paged nor growing nor shareable
by prefix; they live in two more arrays a layer after its pages
(:func:`init_kv_pages`), indexed by the row's STATE SLOT: an id of the pool
(``serving/kvpool.py``), handed to the programs per row beside the block
table, slot 0 the dummy. Prefill (:func:`~marlin_tpu.ops.ssm
.ssd_chunk_scan`) enters a chunk with the slot's state and tail and leaves
them after the chunk's last valid token; a row's first chunk enters with
zeros whatever the slot's last row left (:func:`_enter_state`). Decode
reads and writes each live row's slot once, in place: the state
(:func:`~marlin_tpu.ops.ssm.ssd_decode_update`) and the tail beside it
(:func:`~marlin_tpu.ops.ssm.conv_step_slots`: no tails array is gathered,
scattered or copied in a decode program). A slot of the tails array is whole
tiles in one piece for that, ``((conv - 1) * channels / 128`` rounded up to
8``, 128)`` where the channels are whole lane tiles
(:func:`~marlin_tpu.ops.ssm.tail_slot_shape`; a prefill chunk reshapes the
one row it enters and leaves). The family's
fixed multipliers sit on every
branch (:func:`_parallel_mixers`, :func:`_ssm_mixer`, :func:`_ffn_half`,
:func:`_embed`, :func:`_head_logits`); a model without them traces to the
programs it traced to before.

**A layer without pages** (:class:`DeltaSpec`; the ``olmo_hybrid``
configuration family's ``linear_*`` keys). A ``linear`` layer's only mixer
is recurrent: a gated delta rule (:mod:`~marlin_tpu.ops.delta_rule`) whose
state is a ``key_dim x value_dim`` matrix a head, float32, stored ``(key_dim,
heads * value_dim)`` so that the slab is whole lane tiles, with the tail of
the causal convolution its queries, keys and values pass. It asks ``attend``
for nothing and keeps no keys or values: its two arrays (states, tails) are
indexed by the row's state slot alone, so the global page class covers only
the model's ``full`` layers and the state slot the others. The family's block
(:func:`_post_norm_layer`) norms each branch's OUTPUT (``x + rmsnorm(mixer(
x))``), norms a full layer's queries and keys over the whole projection, and
has no rotary embedding. Prefill (:func:`~marlin_tpu.ops.delta_rule
.delta_chunk_scan`) and decode (:func:`~marlin_tpu.ops.delta_rule
.delta_decode_update`, the tail by :func:`~marlin_tpu.ops.ssm
.conv_step_slots`) meet the slot as a state-space mixer's do: a decode call
reads and writes a row's state and its tail in the slot, in place.

**A layer whose only memory is a convolution's tail** (:class:`ConvSpec`; the
``lfm2_moe`` configuration family's ``conv_*`` keys). A ``conv`` layer's
mixer (:func:`_short_conv`, under the ``short_conv`` scope) is ``[b | c | z]
= u W_in``, a causal depthwise convolution of ``taps`` taps over ``s = b *
z`` (no activation), ``y = (c * conv(s)) W_out``. What it remembers of the
past is the last ``taps - 1`` values of ``s``: ONE array a layer,
``(state_slots, taps - 1, channels)`` in the compute dtype (it is
overwritten every token, never accumulated into), indexed by the row's state
slot as the other mixers' arrays are: the programs, the slot-to-slot copy
and :meth:`ModelSpec.state_slot_bytes` walk the arrays a layer HAS
(:meth:`ConvSpec.slot_arrays`), not a (state, tail) pair. The convolution is
:func:`~marlin_tpu.ops.ssm.causal_conv` / :func:`~marlin_tpu.ops.ssm
.conv_step`, the state-space and delta-rule mixers' own (decode gathers the
live rows' tails and scatters them back: the slot-indexed step of the other
two is not this layer's). The block is the
generic pre-norm one; the family's ``full`` layer norms each head's queries
and keys (one gain of ``head_dim`` for all heads) BEFORE the rotary
embedding and has no head gate (``qk_norm``, ``head_gate``). A slot is tens
of kilobytes where a recurrent matrix makes it tens of megabytes, so a
snapshot costs less than the page it stands behind
(``serving/kvpool.py`` takes one behind every chunk then).

**Attention over a selection, and lightning layers between** (:class:`~marlin_tpu
.ops.sparse_attention.SparseSpec`, :class:`LightningSpec`; the
``minicpm_sala`` configuration family's ``mixer_types``, ``sparse_config``
and ``lightning_*`` keys). A ``sparse`` layer is GQA attention with NO rotary
embedding whose queries, from position ``dense_len`` on, attend ``topk``
blocks of 64 tokens and not the row's whole context
(:mod:`~marlin_tpu.ops.sparse_attention` has the selection's equations). What
it selects BY is a second, smaller cache: a KV head's keys mean-pooled over
windows of 32 tokens 16 apart, a THIRD array a layer beside its K and V
slabs, ``(num_pages, page_len / stride, kv_heads * head_dim)``, indexed by
the same page id (so copy-on-write, prefix sharing and eviction move a
page's compressed keys with the page: :func:`_kv_slabs`, :func:`_copy_entry`),
an entry owned by the page in which its window ENDS (so a shared page's
entries are a function of the shared prefix alone). Prefill writes a
chunk's entries with its pages and applies each query's selection as a mask
inside the key loop; decode completes an entry at the token that ends its
window, selects on the device and hands each (row, KV head) a LIST of blocks
to :func:`~marlin_tpu.ops.paged_attention.paged_decode_attention_blocks`. A
``lightning`` layer (:mod:`~marlin_tpu.ops.lightning`) keeps a ``heads x
head_dim x head_dim`` float32 state in the row's slot (no convolution, no
tail), shared through snapshots as a delta-rule state is. The family's block
(:func:`_sala_layer`) is pre-norm with muP multipliers (:class:`Multipliers`:
``scale_emb`` on the embedding, ``scale_depth / sqrt(mup_denominator)`` on
both branches, ``dim_model_base / hidden_size`` on the logits), per-head
QK-norm and an elementwise sigmoid output gate on both mixers.

**A delta rule that decays a channel, beside held experts** (:class:`KdaSpec`;
the ``solar_open2`` configuration family's ``gqa_layers``,
``linear_attn_config`` and ``kda_*`` keys). A ``kda`` layer is a ``linear``
layer's sibling (Kimi Delta Attention): the same state ``(key_dim, heads *
value_dim)`` float32 and convolution tail in the row's slot, the same two
programs' plumbing, but the decay is one value a CHANNEL of the key
(``diag(a_t) S``), made by a low-rank projection, and the output gate is a
low-rank sigmoid (:func:`_kda_mixer`; :mod:`~marlin_tpu.ops.delta_rule` takes
the decay's rank as the form). The family's ``full`` layer has no positional
term at all and an ELEMENTWISE sigmoid gate on the attention's output
(:func:`_gated_attention`, the ``minicpm_sala`` family's without its
QK-norm); the block is pre-norm (:func:`_solar_layer`) and EVERY layer's FFN
is an expert layer of which a share is held (``first_expert``, ``experts_
held``): the first family in which a layer keeps something in the row's state
slot AND routes over experts it holds a part of.

**A state that is shared by snapshot.** A slot is private to its row, but a
COPY of it at a page boundary is as good to another row as the pages before
that boundary: the pool keeps such copies in further slots of the same
arrays (``serving/kvpool.py``), and :func:`state_slot_copy` is the one
program that takes a snapshot (row slot -> snapshot slot, behind the chunk
that wrote the state) and enters from one (snapshot slot -> row slot, ahead
of the row's first chunk, which then starts past position 0 and so enters
with the slot's contents: :func:`_enter_state`).

**Latent attention** (:class:`LatentSpec`; the DeepSeek-V3 configuration
family's keys). A token's cache entry is not a (K, V) pair per KV head but
ONE vector: the normed down-projection ``c_kv`` (``kv_rank`` values) and a
rotary key ``k_pe`` (``rope_dim`` values) that all heads share. A latent
layer's slab is one array a layer, ``(num_pages, page_len, entry_width)``
(the entry padded with zeros to whole lane tiles,
:attr:`LatentSpec.entry_width`), of the global class (every position, one
table, shareable through the prefix cache like any global page). The two
programs meet it differently, and give the same numbers:

- *prefill* up-projects the gathered latents, ``[k_nope_h | v_h] = c_kv
  W_kvb[h]``, and attends per head: a chunk's queries meet each key once,
  so the fewer operations win (2 x (128 + 128) a head and pair against the
  absorbed form's 2 x (320 + 256)). A chunk of whole lane tiles (a
  multiple of 128 tokens: every engine on the chip) goes through the flash
  kernel (:func:`_attend_latent_flash`); a narrower one through
  :func:`_attend_latent_blocks`, the same arithmetic in plain ``jax.numpy``
  a key block at a time;
- *decode* is absorbed: ``W_kvb`` is never applied to the cache. The query
  takes the key half (``qt_h = q_nope_h W_kvb[h, :, :nope]^T``), meets the
  latent page itself as a key of ``kv_rank + rope_dim`` columns shared by
  all heads, the page's first ``kv_rank`` columns are also the value, and
  the value half of ``W_kvb`` is applied to the attended latent
  (:func:`~marlin_tpu.ops.paged_attention.paged_decode_attention_latent`
  reads each page once, in place; :func:`_attend_latent_gather` is the same
  arithmetic on gathered pages).

**An indexer that picks the tokens a latent layer attends**
(:class:`IndexerSpec` on :class:`LatentSpec`; the ``deepseek_v32`` family's
``index_*`` keys; :mod:`~marlin_tpu.ops.dsa` has the equations). Such a layer
keeps a SECOND memory of a token beside its latent entry: one **index key**
of ``index_head_dim`` values (a LayerNorm of its own projection of the
block's input, rotated rotate-half), in a second page-indexed array a layer,
``(num_pages, page_len, index_head_dim)``, of the global class and indexed by
the same page id, so prefix sharing, copy-on-write and eviction move a
page's index keys with its latents (:func:`_kv_slabs`, :func:`_copy_entry`,
:meth:`ModelSpec.page_values`). A query scores every position it can see,
``I(t, s) = sum_j w_j(t) relu(q^I_j(t) . k^I(s))`` over ``index_n_heads``
index queries made from the query latent (:func:`_index_operands`, under
the ``dsa_index`` scope), takes the ``index_topk`` largest (ties to the
lower position; all positions where fewer exist) and attends THOSE entries
and no other, every head, one softmax over the set
(:func:`_attend_selected_tokens`: the k-th score by a radix search, the mask
and a tile of queries' lists under ``dsa_select``; the gather of a tile's
entries and the absorbed attention over them under ``dsa_attend``). Both
programs use the absorbed form there, each query's own 2048 entries where
the context holds tens of thousands: decode scores a row's index keys in
place (:func:`~marlin_tpu.ops.dsa.index_scores_paged`) and reads the chosen
entries out of the slab; a prefill chunk scores on the MXU
(:func:`~marlin_tpu.ops.dsa.index_scores_chunk`), never gathers or
up-projects the whole context, and holds the gathered entries of
:data:`_DSA_TILE` queries at a time. A chunk wholly below ``index_topk``
keeps the flash path over the context's first ``index_topk`` entries. The
family's expert layers pick group-limited (``n_group``, ``topk_group``:
:func:`~marlin_tpu.models.moe._picks`) and its leading layers are dense
(``first_k_dense_replace``). A configuration without ``index_topk`` has no
indexer, no second array and traces to the programs it traced to before.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.sparse_attention import SparseSpec

__all__ = ["RopeSpec", "IndexerSpec", "LatentSpec", "SsmSpec", "DeltaSpec", "KdaSpec",
           "ConvSpec", "LightningSpec", "SparseSpec", "Multipliers",
           "LayerSpec", "ModelSpec", "init_params", "state_slot_copy",
           "init_layer_params", "init_kv_pages", "window_ring_pages",
           "layer_forward", "prefill_paged", "decode_paged",
           "require_int_heads"]

_MASKED = -1e30  # as ops/paged_attention.py: exp() underflows to exactly 0
#: queries whose gathered entries stand in memory at once in a prefill chunk
#: of a layer with an indexer (x topk x entry_width x 2 B: 84 MB at 32)
_DSA_TILE = 32


def require_int_heads(heads, what: str) -> None:
    """The paths that run only ``transformer.py``'s own block say so."""
    if isinstance(heads, ModelSpec):
        raise TypeError(
            f"{what} runs only the block that `heads: int` describes; a "
            f"ModelSpec is served by the paged path alone "
            f"(ServeEngine(params, spec) with paged=True)")


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """One rotary embedding: ``kind`` ``default`` (``inv_freq_i =
    theta^(-2i/D)``) or ``yarn`` (the interpolated/extrapolated blend below,
    cos and sin scaled by ``attention_factor``), over the first
    ``rotary_dim`` (= D) dimensions of each head, rotate-half form (dimension
    ``i`` turns with ``i + D/2``) or, with ``interleave``, adjacent pairs
    (``2i`` with ``2i + 1``)."""

    theta: float
    rotary_dim: int
    kind: str = "default"
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    interleave: bool = False

    def inv_freq(self) -> np.ndarray:
        D = self.rotary_dim
        i = np.arange(0, D, 2, dtype=np.float64)
        extra = 1.0 / self.theta ** (i / D)
        if self.kind == "default":
            return extra.astype(np.float32)
        if self.kind != "yarn":
            raise ValueError(f"unknown rope kind {self.kind!r}")
        inter = extra / self.factor

        def correction(rotations: float) -> float:
            return (D * math.log(self.original_max
                                 / (rotations * 2 * math.pi))
                    / (2 * math.log(self.theta)))

        low = max(math.floor(correction(self.beta_fast)), 0)
        high = min(math.ceil(correction(self.beta_slow)), D - 1)
        if high == low:
            high += 0.001  # as the published implementation: no 0 / 0
        ramp = np.clip((np.arange(D // 2, dtype=np.float64) - low)
                       / (high - low), 0.0, 1.0)
        return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class IndexerSpec:
    """The sizes of a latent layer's lightning indexer
    (:mod:`~marlin_tpu.ops.dsa`; the ``deepseek_v32`` family's ``index_*``
    keys): ``heads`` index queries of ``head_dim`` a token against ONE index
    key of ``head_dim`` a token (the layer's second page-indexed array), the
    first ``rope_dim`` columns of both rotated (rotate-half), ``topk`` tokens
    a query attends; ``eps`` is the index key's LayerNorm's."""

    heads: int
    head_dim: int
    topk: int
    rope_dim: int
    eps: float = 1e-6


@dataclasses.dataclass(frozen=True)
class LatentSpec:
    """The sizes of a latent-attention layer and its softmax scale:
    ``softmax_scale`` multiplies every score (``(nope_dim + rope_dim)^-1/2``
    times the square of YaRN's ``m``), and query ``i`` is scaled once more by
    ``tau(i) = 1 + scaling_beta * ln(1 + floor(i / scaling_original_max))``
    (1 everywhere where ``scaling_beta`` is 0). With an ``indexer`` a query
    attends the ``indexer.topk`` tokens its index scores rank first and no
    other (all of them where fewer exist)."""

    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    softmax_scale: float
    scaling_beta: float = 0.0
    scaling_original_max: int = 0
    indexer: IndexerSpec | None = None

    @property
    def entry_dim(self) -> int:
        """Values a token leaves in a layer's cache."""
        return self.kv_rank + self.rope_dim

    @property
    def entry_width(self) -> int:
        """Columns of a cache entry as the slab stores it: ``entry_dim``
        rounded up to whole lane tiles of 128, the rest zeros. The chip
        tiles an array's minor dimension by 128 and holds a slab of 320
        columns 384 wide: the decode kernel copies a page out of it in
        whole lane tiles and refuses 320 (a ``BlockSpec`` of 320 made the
        compiler copy the WHOLE slab first); stored padded it is read in place.
        A zero column adds nothing to a score (the query's are zero too)
        and lies past the value's ``kv_rank`` columns."""
        return -(-self.entry_dim // 128) * 128

    def query_scale(self, positions):
        """``softmax_scale * tau(position)``, float32, per query."""
        scale = jnp.full(positions.shape, self.softmax_scale, jnp.float32)
        if self.scaling_beta:
            scale = scale * (1.0 + self.scaling_beta * jnp.log1p(jnp.floor(
                positions.astype(jnp.float32) / self.scaling_original_max)))
        return scale


@dataclasses.dataclass(frozen=True)
class SsmSpec:
    """The sizes of a state-space (Mamba-2) mixer (the ``falcon_h1``
    configuration family's ``mamba_*`` / ``ssm_*`` keys): ``heads`` heads of
    ``head_dim`` channels, each with a state of ``state`` columns a channel;
    ``groups`` groups of heads share one ``B`` and ``C``; a causal depthwise
    convolution of ``conv`` taps before the recurrence; the chunked scan's
    block ``chunk``. ``in_mult`` scales the mixer's input, ``mup`` the five
    segments of its input projection (gate, x, B, C, dt), ``out_mult`` its
    output. The recurrent state is kept in ``state_dtype`` (float32: it is
    multiplied and added to at every token), the convolution's tail in the
    compute dtype."""

    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int
    chunk: int
    in_mult: float = 1.0
    out_mult: float = 1.0
    mup: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    rms_norm: bool = True
    norm_before_gate: bool = False
    state_dtype: str = "float32"

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``[x | B | C]``."""
        return self.d_inner + 2 * self.groups * self.state

    @property
    def segments(self) -> tuple:
        """Widths of the input projection's five segments, in order."""
        gn = self.groups * self.state
        return (self.d_inner, self.d_inner, gn, gn, self.heads)

    def mup_vector(self) -> np.ndarray:
        """``mup`` laid over the input projection's outputs."""
        return np.repeat(np.asarray(self.mup, np.float32), self.segments)

    def slot_arrays(self) -> tuple:
        """What ONE row's slot holds in one layer, ``(shape, dtype)`` an
        array (dtype None: the compute dtype): the recurrent state, then the
        convolution's tail (:func:`~marlin_tpu.ops.ssm.tail_slot_shape`)."""
        from ..ops.ssm import tail_slot_shape

        return (((self.heads, self.state, self.head_dim), self.state_dtype),
                (tail_slot_shape(self.conv, self.conv_dim), None))


@dataclasses.dataclass(frozen=True)
class DeltaSpec:
    """The sizes of a gated delta-rule (linear-attention) mixer (the
    ``olmo_hybrid`` configuration family's ``linear_*`` keys): ``heads``
    heads, keys and queries of ``key_dim`` values and values of
    ``value_dim``, a state of ``key_dim x value_dim`` a head; a causal
    depthwise convolution of ``conv`` taps over ``[q | k | v]``; the chunked
    form's block ``chunk``; ``neg_eigval`` doubles the step ``b`` to ``(0,
    2)``. The recurrent state is kept in ``state_dtype`` (float32), the
    convolution's tail in the compute dtype."""

    heads: int
    key_dim: int
    value_dim: int
    conv: int
    chunk: int = 64
    neg_eigval: bool = True
    state_dtype: str = "float32"

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: ``[q | k | v]``."""
        return self.heads * (2 * self.key_dim + self.value_dim)

    def slot_arrays(self) -> tuple:
        """What ONE row's slot holds in one layer, ``(shape, dtype)`` an
        array (dtype None: the compute dtype): the recurrent state as the
        slab stores it (``(key_dim, heads * value_dim)``: whole lane tiles),
        then the convolution's tail
        (:func:`~marlin_tpu.ops.ssm.tail_slot_shape`)."""
        from ..ops.ssm import tail_slot_shape

        return (((self.key_dim, self.heads * self.value_dim),
                 self.state_dtype),
                (tail_slot_shape(self.conv, self.conv_dim), None))


@dataclasses.dataclass(frozen=True)
class KdaSpec(DeltaSpec):
    """The sizes of a delta-rule mixer whose decay is a vector a head (Kimi
    Delta Attention; the ``solar_open2`` family's ``linear_attn_config`` and
    ``kda_*`` keys): a :class:`DeltaSpec` (state, tail, convolution and
    chunked form alike) with ``key_dim`` decays a head and token. ``rank``
    is the width of the two low-rank projections (decay and output gate;
    ``kda_use_full_proj`` false)."""

    rank: int = 128

    def scan_blocks(self, tokens: int, width: int) -> int:
        """Blocks of the chunk scan ONE layer runs for a chunk ``width``
        positions wide that holds ``tokens`` tokens: those that hold a
        token where the scan's kernel takes these sizes
        (:func:`~marlin_tpu.ops.delta_rule.chunk_scan_supported`), every
        block of the chunk where XLA's form runs."""
        from ..ops import delta_rule

        block = min(self.chunk, width)
        if delta_rule.chunk_scan_supported(
                self.heads, self.key_dim, self.value_dim, block,
                state_dtype=self.state_dtype):
            return -(-tokens // block)
        return width // block


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """The sizes of a gated short-convolution mixer (the ``lfm2_moe``
    configuration family's ``conv_L_cache`` key): a causal depthwise
    convolution of ``taps`` taps over ``channels`` channels, no bias, no
    activation. Its only memory is its tail."""

    taps: int
    channels: int

    #: no chunked scan: a prefill chunk may be of any width
    chunk = 1

    def slot_arrays(self) -> tuple:
        """What ONE row's slot holds in one layer: the convolution's tail
        (the last ``taps - 1`` inputs) in the compute dtype, and NO state
        part."""
        return (((self.taps - 1, self.channels), None),)


@dataclasses.dataclass(frozen=True)
class LightningSpec:
    """The sizes of a lightning-attention mixer (the ``minicpm_sala``
    configuration family's ``lightning_*`` keys): ``heads`` heads of
    ``head_dim`` (keys, queries and values alike; no grouping), a state of
    ``head_dim x head_dim`` a head with a CONSTANT decay a head; the chunked
    form's block ``chunk``. ``first_layer`` and ``layers_total`` place the
    held layers in the published model: the decay of head ``h`` of held
    layer ``i`` is :func:`~marlin_tpu.ops.lightning.lightning_decay` of
    published layer ``first_layer + i`` of ``layers_total``. The state is
    kept in ``state_dtype`` (float32)."""

    heads: int
    head_dim: int
    chunk: int = 64
    first_layer: int = 0
    layers_total: int = 1
    state_dtype: str = "float32"

    def slot_arrays(self) -> tuple:
        """What ONE row's slot holds in one layer: the recurrent state
        ``(heads, head_dim, head_dim)``, and no tail."""
        return (((self.heads, self.head_dim, self.head_dim),
                 self.state_dtype),)


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """The fixed scalars a configuration family puts on its branches (the
    ``falcon_h1`` family's ``*_multiplier`` keys): on the embedding, on the
    attention's input, keys and output, inside and after the FFN, on the
    logits."""

    embedding: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    mlp_gate: float = 1.0
    mlp_down: float = 1.0
    lm_head: float = 1.0


#: the kinds of layer (``LayerSpec.attn``): kind -> (a page id indexes some
#: array of the layer, the layer keeps something in the row's state slot).
#: One line a kind: ``full`` / ``sliding`` (K and V per KV head), ``latent``
#: (one entry a token), ``sparse`` (K, V and the compressed keys a sparse
#: layer selects by), ``linear`` (a gated delta rule: state and tail),
#: ``conv`` (a short convolution: a tail), ``lightning`` (a state), ``kda``
#: (a delta rule that decays a channel: state and tail)
_LAYER_KINDS = {
    "full": (True, False),
    "sliding": (True, False),
    "latent": (True, False),
    "sparse": (True, False),
    "linear": (False, True),
    "conv": (False, True),
    "lightning": (False, True),
    "kda": (False, True),
}


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    #: a key of :data:`_LAYER_KINDS`: what the layer's mixer is and what it
    #: remembers the past in
    attn: str
    q_heads: int
    ffn: str       # "dense" | "moe"
    #: a state-space mixer (:class:`SsmSpec`) beside the attention, both on
    #: the one normed input, their outputs added
    ssm: bool = False

    @property
    def owns_pages(self) -> bool:
        """Whether a page id indexes any array of the layer
        (:data:`_LAYER_KINDS`)."""
        return _LAYER_KINDS[self.attn][0]

    @property
    def has_state(self) -> bool:
        """Whether the layer keeps anything in the row's state slot
        (:data:`_LAYER_KINDS`; or a state-space mixer beside its
        attention)."""
        return self.ssm or _LAYER_KINDS[self.attn][1]


_LAYER_TYPES_KEYS = (
    "num_hidden_layers", "layer_types", "head_dim", "hidden_size",
    "num_key_value_heads", "sliding_window", "rope_parameters",
    "intermediate_size", "moe_intermediate_size",
    "shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
    "vocab_size")
_LATENT_KEYS = (
    "num_hidden_layers", "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim", "q_lora_rank", "v_head_dim",
    "n_routed_experts", "moe_intermediate_size", "hidden_size",
    "num_key_value_heads", "intermediate_size", "num_experts_per_tok",
    "vocab_size")
_FALCON_H1_KEYS = (
    "num_hidden_layers", "hidden_size", "head_dim", "num_attention_heads",
    "num_key_value_heads", "rope_theta", "intermediate_size", "vocab_size",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "mamba_chunk_size")
_OLMO_HYBRID_KEYS = (
    "num_hidden_layers", "layer_types", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "vocab_size",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim")
_LFM2_KEYS = (
    "num_hidden_layers", "layer_types", "hidden_size", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "vocab_size", "conv_L_cache",
    "num_dense_layers", "moe_intermediate_size", "num_experts",
    "num_experts_per_tok")


_SALA_KEYS = (
    "num_hidden_layers", "mixer_types", "hidden_size", "head_dim",
    "num_attention_heads", "num_key_value_heads", "intermediate_size",
    "vocab_size", "lightning_nh", "lightning_nkv", "lightning_head_dim",
    "rope_theta", "scale_emb", "scale_depth", "mup_denominator",
    "dim_model_base", "sparse_config")
_SOLAR_OPEN2_KEYS = (
    "num_hidden_layers", "hidden_size", "head_dim", "num_attention_heads",
    "num_key_value_heads", "vocab_size", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
    "gqa_layers", "linear_attn_config", "use_gqa_gate", "use_rope",
    "kda_use_full_proj", "kda_allow_neg_eigval")
_LINEAR_ATTN_CONFIG_KEYS = ("short_conv_kernel_size", "head_dim", "num_heads",
                            "num_kv_heads")
_SPARSE_CONFIG_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk",
                       "init_blocks", "window_size", "dense_len")


def _require(cfg: dict, keys: tuple, family: str) -> None:
    """A ``ValueError`` that names every key of ``keys`` ``cfg`` lacks."""
    missing = [k for k in keys if k not in cfg]
    if missing:
        raise ValueError(
            f"ModelSpec.from_config read this configuration as of the "
            f"{family} family and could not read its keys {missing}")


def _experts_total(held: int, experts_total, first_expert: int) -> int:
    """The router's width for a share of ``held`` experts from
    ``first_expert`` on (``experts_total`` None: all are held)."""
    total = held if experts_total is None else int(experts_total)
    if not 0 <= first_expert <= total - held:
        raise ValueError(f"experts [{first_expert}, "
                         f"{first_expert + held}) are not among {total}")
    return total


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Everything the programs need to know of a model that is not in the
    parameters' shapes. Hashable: static in ``jit``."""

    d_model: int
    head_dim: int
    kv_heads: int
    layers: tuple
    window: int
    rope_full: RopeSpec
    rope_sliding: RopeSpec
    dense_width: int
    expert_width: int
    shared_width: int
    n_experts: int        # the router's width: every expert of the model
    experts_held: int     # how many of them live here ...
    first_expert: int     # ... starting at this one
    top_k: int
    routed_scale: float
    vocab_held: int
    norm_eps: float = 1e-6
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    #: the expert layers' scoring: ``softmax`` over the router's outputs, or
    #: ``sigmoid`` of each with a per-expert bias that only selects
    scoring: str = "softmax"
    #: the latent layers' sizes (their rotary embedding is ``rope_full``)
    latent: LatentSpec | None = None
    #: the state-space mixers' sizes (the layers with ``ssm``)
    ssm: SsmSpec | None = None
    #: the family's fixed branch multipliers (None: there are none)
    mults: Multipliers | None = None
    #: the delta-rule mixers' sizes (the ``linear`` layers); with it the
    #: block is the ``olmo_hybrid`` family's (:func:`_post_norm_layer`)
    delta: DeltaSpec | None = None
    #: the short convolutions' sizes (the ``conv`` layers)
    conv: ConvSpec | None = None
    #: the block selection's sizes (the ``sparse`` layers) and the lightning
    #: mixers' (the ``lightning`` layers); with them the block is the
    #: ``minicpm_sala`` family's (:func:`_sala_layer`)
    sparse: SparseSpec | None = None
    lightning: LightningSpec | None = None
    #: a ``full`` / ``sliding`` layer's per-head sigmoid gate on the
    #: attention output, and an RMSNorm over each head's queries and keys
    #: (one gain of ``head_dim`` for all heads) before the rotary embedding
    head_gate: bool = True
    qk_norm: bool = False
    #: the mixers' sizes of the ``kda`` layers; with it the block is the
    #: ``solar_open2`` family's (:func:`_solar_layer`)
    kda: KdaSpec | None = None
    #: the head is the embedding itself (no ``head`` array)
    tied_head: bool = False
    #: added to the sum the picks' weights are renormalised by
    renorm_eps: float = 0.0
    #: group-limited routing: the experts in ``n_group`` groups of which the
    #: ``topk_group`` best (by the sum of a group's two largest biased
    #: scores) stay eligible; (1, 1): every expert is
    n_group: int = 1
    topk_group: int = 1

    @classmethod
    def from_config(cls, cfg: dict, experts_total: int | None = None,
                    first_expert: int = 0) -> "ModelSpec":
        """From the published keys (``hidden_size``, ``head_dim``,
        ``layer_types``, ``num_attention_heads_per_layer``,
        ``mlp_layer_types``, ``rope_parameters``, ...). ``num_hidden_layers``
        layers are taken from the front of the per-layer lists;
        ``num_experts`` and ``vocab_size`` are what is held here.
        ``experts_total`` is the router's width where the configuration holds
        a share of the experts (default: all are held), ``first_expert`` the
        first one of the share. A configuration with ``kv_lora_rank`` is of
        the latent-attention family (:meth:`_from_latent_config`), one with
        ``mamba_d_ssm`` of the ``falcon_h1`` family
        (:meth:`_from_falcon_h1_config`), one with ``linear_key_head_dim``
        of the ``olmo_hybrid`` family (:meth:`_from_olmo_hybrid_config`), one
        with ``conv_L_cache`` of the ``lfm2_moe`` family
        (:meth:`_from_lfm2_config`), one with ``mixer_types`` of the
        ``minicpm_sala`` family (:meth:`_from_sala_config`), one with
        ``gqa_layers`` of the ``solar_open2`` family
        (:meth:`_from_solar_open2_config`). A configuration that lacks keys
        its family needs raises a ``ValueError`` that names them."""
        if "kv_lora_rank" in cfg:
            _require(cfg, _LATENT_KEYS + (
                ("rope_parameters",) if "rope_theta" not in cfg
                else ("rope_scaling",)), "latent-attention (kv_lora_rank)")
            return cls._from_latent_config(cfg, experts_total, first_expert)
        if "mamba_d_ssm" in cfg:
            _require(cfg, _FALCON_H1_KEYS, "falcon_h1 (mamba_d_ssm)")
            return cls._from_falcon_h1_config(cfg)
        if "linear_key_head_dim" in cfg:
            _require(cfg, _OLMO_HYBRID_KEYS,
                     "olmo_hybrid (linear_key_head_dim)")
            return cls._from_olmo_hybrid_config(cfg)
        if "conv_L_cache" in cfg:
            _require(cfg, _LFM2_KEYS, "lfm2_moe (conv_L_cache)")
            return cls._from_lfm2_config(cfg, experts_total, first_expert)
        if "mixer_types" in cfg:
            _require(cfg, _SALA_KEYS, "minicpm_sala (mixer_types)")
            _require(cfg["sparse_config"], _SPARSE_CONFIG_KEYS,
                     "minicpm_sala (mixer_types), in its sparse_config,")
            return cls._from_sala_config(cfg)
        if "gqa_layers" in cfg:
            _require(cfg, _SOLAR_OPEN2_KEYS, "solar_open2 (gqa_layers)")
            _require(cfg["linear_attn_config"], _LINEAR_ATTN_CONFIG_KEYS,
                     "solar_open2 (gqa_layers), in its linear_attn_config,")
            return cls._from_solar_open2_config(cfg, experts_total,
                                                first_expert)
        _require(cfg, _LAYER_TYPES_KEYS, "layer_types")
        n = int(cfg["num_hidden_layers"])
        kinds = {"full_attention": "full", "sliding_attention": "sliding"}
        heads = cfg.get("num_attention_heads_per_layer") or (
            [cfg["num_attention_heads"]] * n)
        ffns = cfg.get("mlp_layer_types") or (
            ["dense" if i in cfg.get("mlp_only_layers", ()) else "sparse"
             for i in range(n)])
        layers = tuple(
            LayerSpec(kinds[cfg["layer_types"][i]], int(heads[i]),
                      "dense" if ffns[i] == "dense" else "moe")
            for i in range(n))
        dh = int(cfg["head_dim"])

        def rope(p: dict) -> RopeSpec:
            D = int(round(dh * float(p.get("partial_rotary_factor", 1.0))))
            return RopeSpec(
                theta=float(p["rope_theta"]), rotary_dim=D,
                kind=p.get("rope_type", "default"),
                factor=float(p.get("factor", 1.0)),
                original_max=int(p.get("original_max_position_embeddings",
                                       0)),
                beta_fast=float(p.get("beta_fast", 32.0)),
                beta_slow=float(p.get("beta_slow", 1.0)),
                attention_factor=float(p.get("attention_factor", 1.0)))

        held = int(cfg["num_experts"])
        total = _experts_total(held, experts_total, first_expert)
        return cls(
            d_model=int(cfg["hidden_size"]), head_dim=dh,
            kv_heads=int(cfg["num_key_value_heads"]), layers=layers,
            window=int(cfg["sliding_window"]),
            rope_full=rope(cfg["rope_parameters"]["full_attention"]),
            rope_sliding=rope(cfg["rope_parameters"]["sliding_attention"]),
            dense_width=int(cfg["intermediate_size"]),
            expert_width=int(cfg["moe_intermediate_size"]),
            shared_width=int(cfg["shared_expert_intermediate_size"]),
            n_experts=total, experts_held=held,
            first_expert=int(first_expert),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scale=float(cfg.get("moe_routed_scaling_factor", 1.0)),
            vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")))

    @classmethod
    def _from_latent_config(cls, cfg: dict, experts_total, first_expert):
        """The DeepSeek-V3 family's keys: every layer ``latent``
        (``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``,
        ``qk_rope_head_dim``, ``v_head_dim``), dense before
        ``first_k_dense_replace`` and an expert layer from it on
        (``n_routed_experts`` held here, ``n_shared_experts`` x
        ``moe_intermediate_size`` shared, sigmoid scoring), YaRN on the
        rotary dimensions with ``mscale`` / ``mscale_all_dim``, and
        ``llama_4_scaling_beta`` for the position-dependent query scale. The
        rotary embedding's keys are ``rope_parameters`` or, where a file has
        the family's published pair instead, ``rope_scaling`` (its ``type``)
        with ``rope_theta``. ``n_group`` / ``topk_group``: group-limited
        picks. ``index_topk`` (with ``index_n_heads``, ``index_head_dim``):
        every layer has a lightning indexer (:class:`IndexerSpec`)."""
        if not cfg.get("norm_topk_prob", True):
            raise ValueError("picks that are not renormalised "
                             "(norm_topk_prob false) are not built")
        n = int(cfg["num_hidden_layers"])
        dense_first = int(cfg.get("first_k_dense_replace", 0))
        layers = tuple(
            LayerSpec("latent", int(cfg["num_attention_heads"]),
                      "dense" if i < dense_first else "moe")
            for i in range(n))
        if "rope_parameters" in cfg:
            rp = cfg["rope_parameters"]
        else:
            rp = {**(cfg.get("rope_scaling") or {}),
                  "rope_theta": cfg["rope_theta"]}
            rp["rope_type"] = rp.pop("type", "default")
        factor = float(rp.get("factor", 1.0))
        yarn = rp.get("rope_type", "default") == "yarn"

        def mscale(m: float) -> float:
            return 0.1 * m * math.log(factor) + 1.0 if yarn and factor > 1 \
                else 1.0

        nope, rdim = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
        m_all = mscale(float(rp.get("mscale_all_dim", 0.0)))
        rope = RopeSpec(
            theta=float(rp["rope_theta"]), rotary_dim=rdim,
            kind="yarn" if yarn else "default", factor=factor,
            original_max=int(rp.get("original_max_position_embeddings", 0)),
            beta_fast=float(rp.get("beta_fast", 32.0)),
            beta_slow=float(rp.get("beta_slow", 1.0)),
            attention_factor=mscale(float(rp.get("mscale", 1.0))) / m_all,
            interleave=bool(cfg.get("rope_interleave", False)))
        latent = LatentSpec(
            q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
            nope_dim=nope, rope_dim=rdim, v_dim=int(cfg["v_head_dim"]),
            softmax_scale=(nope + rdim) ** -0.5 * m_all * m_all,
            scaling_beta=float(rp.get("llama_4_scaling_beta", 0.0)),
            scaling_original_max=int(
                rp.get("original_max_position_embeddings", 0)),
            indexer=None if "index_topk" not in cfg else IndexerSpec(
                heads=int(cfg["index_n_heads"]),
                head_dim=int(cfg["index_head_dim"]),
                topk=int(cfg["index_topk"]), rope_dim=rdim,
                eps=float(cfg.get("index_norm_eps", 1e-6))))
        held = int(cfg["n_routed_experts"])
        total = _experts_total(held, experts_total, first_expert)
        n_group = int(cfg.get("n_group", 1))
        if total % n_group or not 0 < int(cfg.get("topk_group", 1)) <= n_group:
            raise ValueError(f"{total} experts are not {n_group} whole "
                             f"groups of which topk_group stay")
        width = int(cfg["moe_intermediate_size"])
        return cls(
            d_model=int(cfg["hidden_size"]), head_dim=nope + rdim,
            kv_heads=int(cfg["num_key_value_heads"]), layers=layers,
            window=0, rope_full=rope, rope_sliding=rope,
            dense_width=int(cfg["intermediate_size"]), expert_width=width,
            shared_width=width * int(cfg.get("n_shared_experts", 1)),
            n_experts=total, experts_held=held,
            first_expert=int(first_expert),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
            vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")),
            scoring="sigmoid", latent=latent, n_group=n_group,
            topk_group=int(cfg.get("topk_group", 1)))

    @classmethod
    def _from_falcon_h1_config(cls, cfg: dict):
        """The ``falcon_h1`` family's keys: every layer full attention (GQA,
        the rotary embedding over the whole head) AND a Mamba-2 mixer
        (``mamba_*``) side by side on one normed input, then a dense SwiGLU
        FFN; fixed multipliers on every branch (``*_multiplier``,
        ``ssm_multipliers``, ``mlp_multipliers``); no expert layer, no
        window. ``ssm_state_dtype`` (not a published key; default float32)
        is the dtype the recurrent state is kept in."""
        unbuilt = [k for k in ("attention_bias", "mamba_proj_bias",
                               "mlp_bias", "projectors_bias") if cfg.get(k)]
        if unbuilt or not cfg.get("mamba_conv_bias", True) \
                or cfg.get("rope_scaling"):
            raise ValueError(f"not built: projection biases {unbuilt}, a "
                             f"convolution without bias, or rope_scaling")
        n = int(cfg["num_hidden_layers"])
        heads, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
        if heads * hd != int(cfg["mamba_d_ssm"]):
            raise ValueError("mamba_n_heads x mamba_d_head != mamba_d_ssm")
        dh = int(cfg["head_dim"])
        rope = RopeSpec(theta=float(cfg["rope_theta"]), rotary_dim=dh)
        gate_mult, down_mult = cfg.get("mlp_multipliers", (1.0, 1.0))
        ssm = SsmSpec(
            heads=heads, head_dim=hd, state=int(cfg["mamba_d_state"]),
            groups=int(cfg["mamba_n_groups"]), conv=int(cfg["mamba_d_conv"]),
            chunk=int(cfg["mamba_chunk_size"]),
            in_mult=float(cfg.get("ssm_in_multiplier", 1.0)),
            out_mult=float(cfg.get("ssm_out_multiplier", 1.0)),
            mup=tuple(float(v) for v in cfg.get("ssm_multipliers",
                                                (1.0,) * 5)),
            rms_norm=bool(cfg.get("mamba_rms_norm", True)),
            norm_before_gate=bool(cfg.get("mamba_norm_before_gate", False)),
            state_dtype=str(cfg.get("ssm_state_dtype", "float32")))
        mults = Multipliers(
            embedding=float(cfg.get("embedding_multiplier", 1.0)),
            attention_in=float(cfg.get("attention_in_multiplier", 1.0)),
            attention_out=float(cfg.get("attention_out_multiplier", 1.0)),
            key=float(cfg.get("key_multiplier", 1.0)),
            mlp_gate=float(gate_mult), mlp_down=float(down_mult),
            lm_head=float(cfg.get("lm_head_multiplier", 1.0)))
        layer = LayerSpec("full", int(cfg["num_attention_heads"]), "dense",
                          ssm=True)
        return cls(
            d_model=int(cfg["hidden_size"]), head_dim=dh,
            kv_heads=int(cfg["num_key_value_heads"]), layers=(layer,) * n,
            window=0, rope_full=rope, rope_sliding=rope,
            dense_width=int(cfg["intermediate_size"]), expert_width=0,
            shared_width=0, n_experts=0, experts_held=0, first_expert=0,
            top_k=0, routed_scale=1.0, vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")),
            ssm=ssm, mults=mults)

    @classmethod
    def _from_olmo_hybrid_config(cls, cfg: dict):
        """The ``olmo_hybrid`` family's keys: ``layer_types`` of
        ``linear_attention`` (a gated delta-rule mixer, ``linear_*``, and no
        attention: the layer owns no page) and ``full_attention`` (MHA or
        GQA, queries and keys normed over the whole projection, NO rotary
        embedding: ``rope_parameters.rope_theta`` is null), a dense SwiGLU
        after either, every branch's OUTPUT normed before it joins the
        stream. ``linear_chunk_size`` (the chunked form's block, default 64)
        and ``linear_state_dtype`` (default float32) are not published
        keys."""
        theta = (cfg.get("rope_parameters") or {}).get("rope_theta")
        if cfg.get("attention_bias") or theta is not None \
                or cfg.get("hidden_act", "silu") != "silu" \
                or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
            raise ValueError(
                "not built for the olmo_hybrid family: attention_bias, a "
                "rotary embedding (rope_theta not null), an activation "
                "other than silu, or value heads grouped over key heads")
        n = int(cfg["num_hidden_layers"])
        kinds = {"full_attention": "full", "linear_attention": "linear"}
        heads = int(cfg["num_attention_heads"])
        layers = tuple(LayerSpec(kinds[cfg["layer_types"][i]], heads, "dense")
                       for i in range(n))
        delta = DeltaSpec(
            heads=int(cfg["linear_num_key_heads"]),
            key_dim=int(cfg["linear_key_head_dim"]),
            value_dim=int(cfg["linear_value_head_dim"]),
            conv=int(cfg["linear_conv_kernel_dim"]),
            chunk=int(cfg.get("linear_chunk_size", 64)),
            neg_eigval=bool(cfg.get("linear_allow_neg_eigval", False)),
            state_dtype=str(cfg.get("linear_state_dtype", "float32")))
        dh = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // heads)
        none = RopeSpec(theta=1.0, rotary_dim=0)   # never applied
        return cls(
            d_model=int(cfg["hidden_size"]), head_dim=dh,
            kv_heads=int(cfg["num_key_value_heads"]), layers=layers,
            window=0, rope_full=none, rope_sliding=none,
            dense_width=int(cfg["intermediate_size"]), expert_width=0,
            shared_width=0, n_experts=0, experts_held=0, first_expert=0,
            top_k=0, routed_scale=1.0, vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")),
            delta=delta)

    @classmethod
    def _from_lfm2_config(cls, cfg: dict, experts_total, first_expert):
        """The ``lfm2_moe`` family's keys: ``layer_types`` of ``conv`` (a
        gated short convolution of ``conv_L_cache`` taps over ``hidden_size``
        channels and no attention: the layer owns no page and keeps only
        the convolution's tail) and ``full_attention`` (GQA, each head's
        queries and keys normed before the rotary embedding over the whole
        head, no gate); ``num_dense_layers`` leading dense SwiGLU layers
        (``intermediate_size``), then expert layers (``num_experts`` x
        ``moe_intermediate_size``, sigmoid scoring, ``use_expert_bias``: a
        bias that only selects, the picks renormalised over their sum +
        1e-6, NO shared expert); ``head_dim`` = hidden / heads where absent;
        a tied head unless ``tie_word_embeddings`` is false."""
        unbuilt = [k for k, bad in (
            ("conv_bias", cfg.get("conv_bias", False)),
            ("num_shared_experts", cfg.get("num_shared_experts", 0)),
            ("norm_topk_prob", not cfg.get("norm_topk_prob", True)),
            ("use_expert_bias", not cfg.get("use_expert_bias", True)),
            ("rope_scaling", cfg.get("rope_scaling")),
            ("attention_bias", cfg.get("attention_bias", False))) if bad]
        kinds = {"full_attention": "full", "conv": "conv"}
        n = int(cfg["num_hidden_layers"])
        unknown = sorted(set(cfg["layer_types"][:n]) - set(kinds))
        if unbuilt or unknown:
            raise ValueError(
                f"not built for the lfm2_moe family: the keys {unbuilt} as "
                f"this configuration sets them (a convolution bias, a shared "
                f"expert, picks not renormalised, a router without its "
                f"selection bias, rope_scaling, attention_bias), layer "
                f"types {unknown}")
        heads, d = int(cfg["num_attention_heads"]), int(cfg["hidden_size"])
        dense_first = int(cfg["num_dense_layers"])
        layers = tuple(
            LayerSpec(kinds[cfg["layer_types"][i]], heads,
                      "dense" if i < dense_first else "moe")
            for i in range(n))
        dh = int(cfg.get("head_dim") or d // heads)
        theta = cfg.get("rope_theta") or (
            cfg.get("rope_parameters") or {}).get("rope_theta")
        if theta is None:
            raise ValueError("ModelSpec.from_config read this configuration "
                             "as of the lfm2_moe family and could not read "
                             "its keys ['rope_theta']")
        rope = RopeSpec(theta=float(theta), rotary_dim=dh)
        held = int(cfg["num_experts"])
        total = _experts_total(held, experts_total, first_expert)
        return cls(
            d_model=d, head_dim=dh,
            kv_heads=int(cfg["num_key_value_heads"]), layers=layers,
            window=0, rope_full=rope, rope_sliding=rope,
            dense_width=int(cfg["intermediate_size"]),
            expert_width=int(cfg["moe_intermediate_size"]), shared_width=0,
            n_experts=total, experts_held=held,
            first_expert=int(first_expert),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
            vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("norm_eps", 1e-5)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")),
            scoring="sigmoid",
            conv=ConvSpec(taps=int(cfg["conv_L_cache"]), channels=d),
            head_gate=False, qk_norm=True,
            tied_head=bool(cfg.get("tie_word_embeddings", True)),
            renorm_eps=1e-6)

    @classmethod
    def _from_sala_config(cls, cfg: dict):
        """The ``minicpm_sala`` family's keys: ``mixer_types`` of
        ``minicpm4`` (GQA attention with NO rotary embedding, dense below
        ``sparse_config.dense_len`` and over ``topk`` selected blocks from
        it on: a ``sparse`` layer) and ``lightning-attn`` (linear attention
        with a constant decay a head, the rotary embedding over the whole
        head: a ``lightning`` layer, which owns no page); per-head QK-norm
        and an elementwise sigmoid output gate on both; a dense SwiGLU after
        either; muP multipliers (``scale_emb``, ``scale_depth /
        sqrt(mup_denominator)`` on both branches, ``dim_model_base /
        hidden_size`` on the logits); an untied head. ``num_hidden_layers``
        layers are taken from the FRONT of ``mixer_types``; ``first_layer``
        (default 0) is the published index of the first of them and
        ``mup_denominator`` the published depth (the decay's and the
        branches' scale stay the published model's when fewer layers are
        held). ``sparse_config`` (the MiniCPM4 family's), ``lightning_chunk_
        size`` (default 64) and ``lightning_state_dtype`` (default float32)
        are not published keys of this model."""
        n = int(cfg["num_hidden_layers"])
        kinds = {"minicpm4": "sparse", "lightning-attn": "lightning"}
        sc = cfg["sparse_config"]
        unknown = sorted(set(cfg["mixer_types"][:n]) - set(kinds))
        unbuilt = [k for k, bad in (
            ("attention_bias", cfg.get("attention_bias", False)),
            ("attn_use_rope", cfg.get("attn_use_rope", False)),
            ("lightning_use_rope", not cfg.get("lightning_use_rope", True)),
            ("qk_norm", not cfg.get("qk_norm", True)),
            ("use_output_gate", not cfg.get("use_output_gate", True)),
            ("use_output_norm", not cfg.get("use_output_norm", True)),
            ("attn_use_output_gate",
             not cfg.get("attn_use_output_gate", True)),
            ("tie_word_embeddings", cfg.get("tie_word_embeddings", False)),
            ("hidden_act", cfg.get("hidden_act", "silu") != "silu"),
            ("lightning_nkv", cfg["lightning_nkv"] != cfg["lightning_nh"]),
            ("lightning_head_dim",
             cfg["lightning_head_dim"] != cfg["head_dim"]),
            ("sparse_config.kernel_size",
             sc["kernel_size"] != 2 * sc["kernel_stride"])) if bad]
        if unbuilt or unknown:
            raise ValueError(
                f"not built for the minicpm_sala family: the keys {unbuilt} "
                f"as this configuration sets them (a projection bias, a "
                f"rotary embedding in the sparse layers or none in the "
                f"lightning ones, no QK-norm, a mixer without its output "
                f"gate or norm, a tied head, an activation other than silu, "
                f"grouped lightning heads or their own head size, pooling "
                f"windows other than two strides wide), mixer types "
                f"{unknown}")
        heads, dh = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
        layers = tuple(LayerSpec(kinds[cfg["mixer_types"][i]], heads, "dense")
                       for i in range(n))
        depth = int(cfg["mup_denominator"])
        r = float(cfg["scale_depth"]) / math.sqrt(depth)
        mults = Multipliers(
            embedding=float(cfg["scale_emb"]), attention_out=r, mlp_down=r,
            lm_head=float(cfg["dim_model_base"]) / float(cfg["hidden_size"]))
        rope = RopeSpec(theta=float(cfg["rope_theta"]), rotary_dim=dh)
        return cls(
            d_model=int(cfg["hidden_size"]), head_dim=dh,
            kv_heads=int(cfg["num_key_value_heads"]), layers=layers,
            window=0, rope_full=rope, rope_sliding=rope,
            dense_width=int(cfg["intermediate_size"]), expert_width=0,
            shared_width=0, n_experts=0, experts_held=0, first_expert=0,
            top_k=0, routed_scale=1.0, vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-6)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")),
            mults=mults, head_gate=False, qk_norm=True,
            sparse=SparseSpec(
                stride=int(sc["kernel_stride"]), block=int(sc["block_size"]),
                topk=int(sc["topk"]), init_blocks=int(sc["init_blocks"]),
                window=int(sc["window_size"]), dense_len=int(sc["dense_len"])),
            lightning=LightningSpec(
                heads=int(cfg["lightning_nh"]),
                head_dim=int(cfg["lightning_head_dim"]),
                chunk=int(cfg.get("lightning_chunk_size", 64)),
                first_layer=int(cfg.get("first_layer", 0)), layers_total=depth,
                state_dtype=str(cfg.get("lightning_state_dtype", "float32"))))

    @classmethod
    def _from_solar_open2_config(cls, cfg: dict, experts_total,
                                 first_expert):
        """The ``solar_open2`` family's keys: the layers named in
        ``gqa_layers`` are GQA attention with NO positional term (``use_rope``
        false) and an elementwise sigmoid gate on the attention's output
        (``use_gqa_gate``): ``full`` layers; every other layer is a ``kda``
        layer (``linear_attn_config``: ``num_heads`` heads of ``head_dim``
        for keys and values alike, ``num_kv_heads`` null, a convolution of
        ``short_conv_kernel_size`` taps; ``kda_allow_neg_eigval`` doubles
        the step, ``kda_use_full_proj`` false makes decay and gate low-rank
        projections). EVERY layer from ``first_k_dense_replace`` (0) on is an
        expert layer in the DeepSeek-V3 family's keys (``n_routed_experts``
        held here, ``n_shared_experts`` x ``moe_intermediate_size`` shared,
        sigmoid scoring, a bias that only selects); an untied head.
        ``kda_gate_rank`` (default: the mixer's ``head_dim``),
        ``kda_chunk_size`` (default 64) and ``kda_state_dtype`` (default
        float32) are not published keys."""
        la = cfg["linear_attn_config"]
        unbuilt = [k for k, bad in (
            ("use_rope", cfg["use_rope"]),
            ("use_gqa_gate", not cfg["use_gqa_gate"]),
            ("kda_use_full_proj", cfg["kda_use_full_proj"]),
            ("linear_attn_config.num_kv_heads",
             la["num_kv_heads"] not in (None, la["num_heads"])),
            ("first_k_dense_replace", cfg.get("first_k_dense_replace", 0)),
            ("norm_topk_prob", not cfg.get("norm_topk_prob", True)),
            ("n_group", int(cfg.get("n_group", 1)) != 1),
            ("attention_bias", cfg.get("attention_bias", False)),
            ("tie_word_embeddings", cfg.get("tie_word_embeddings", False)),
            ("hidden_act", cfg.get("hidden_act", "silu") != "silu")) if bad]
        if unbuilt:
            raise ValueError(
                f"not built for the solar_open2 family: the keys {unbuilt} "
                f"as this configuration sets them (a rotary embedding in the "
                f"GQA layers, a GQA layer without its gate, full-rank decay "
                f"and gate projections, value heads grouped over key heads, "
                f"leading dense layers, picks not renormalised, expert "
                f"groups, a projection bias, a tied head, an activation "
                f"other than silu)")
        heads = int(cfg["num_attention_heads"])
        gqa = set(int(i) for i in cfg["gqa_layers"])
        layers = tuple(LayerSpec("full" if i in gqa else "kda", heads, "moe")
                       for i in range(int(cfg["num_hidden_layers"])))
        kd = int(la["head_dim"])
        kda = KdaSpec(
            heads=int(la["num_heads"]), key_dim=kd, value_dim=kd,
            conv=int(la["short_conv_kernel_size"]),
            chunk=int(cfg.get("kda_chunk_size", 64)),
            neg_eigval=bool(cfg["kda_allow_neg_eigval"]),
            state_dtype=str(cfg.get("kda_state_dtype", "float32")),
            rank=int(cfg.get("kda_gate_rank", kd)))
        held = int(cfg["n_routed_experts"])
        total = _experts_total(held, experts_total, first_expert)
        width = int(cfg["moe_intermediate_size"])
        none = RopeSpec(theta=1.0, rotary_dim=0)   # never applied
        return cls(
            d_model=int(cfg["hidden_size"]), head_dim=int(cfg["head_dim"]),
            kv_heads=int(cfg["num_key_value_heads"]), layers=layers,
            window=0, rope_full=none, rope_sliding=none,
            dense_width=int(cfg.get("intermediate_size", 0)),
            expert_width=width,
            shared_width=width * int(cfg["n_shared_experts"]),
            n_experts=total, experts_held=held,
            first_expert=int(first_expert),
            top_k=int(cfg["num_experts_per_tok"]),
            routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
            vocab_held=int(cfg["vocab_size"]),
            norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            param_dtype=str(cfg.get("param_dtype", "bfloat16")),
            compute_dtype=str(cfg.get("compute_dtype", "bfloat16")),
            scoring="sigmoid", kda=kda, head_gate=False)

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def layer_names(self, attn: str) -> list:
        return [f"l{i}" for i, ly in enumerate(self.layers)
                if ly.attn == attn]

    @property
    def has_window(self) -> bool:
        return any(ly.attn == "sliding" for ly in self.layers)

    @property
    def has_state(self) -> bool:
        """Whether a row holds a recurrent-state slot beside its pages."""
        return any(ly.has_state for ly in self.layers)

    @property
    def mixer(self):
        """The sizes of the mixers that keep something in a state slot, of
        whichever kind (None: no layer has one): what a slot is sized by."""
        return (self.ssm or self.delta or self.kda or self.conv
                or self.lightning)

    def state_slot_bytes(self, compute_dtype: str | None = None) -> int:
        """Bytes ONE row's state slot holds over all layers: each array a
        layer with a mixer keeps there (:meth:`slot_arrays` of its spec: a
        recurrent state in its own dtype, a convolution's tail in the
        compute dtype; 0 for a model without)."""
        if not self.has_state:
            return 0
        cd = jnp.dtype(compute_dtype or self.compute_dtype)
        per_layer = sum(math.prod(shape) * jnp.dtype(dt or cd).itemsize
                        for shape, dt in self.mixer.slot_arrays())
        return per_layer * sum(ly.has_state for ly in self.layers)

    def page_values(self, kind: str, page_len: int) -> int:
        """Cache values ONE page id of class ``kind`` holds over all layers
        (``full``: the global class, the full, the latent and the sparse
        layers; ``sliding``: the window class): K and V per KV head for a
        full or a sliding layer, one latent entry for a latent one (and its
        index key, where the layer has an indexer), K, V and the compressed
        keys (``1 / stride`` of an entry a token) for a sparse one."""
        kinds = ("sliding",) if kind == "sliding" else (
            "full", "latent", "sparse")
        kv = self.kv_heads * self.head_dim
        ix = self.latent and self.latent.indexer
        return sum(
            page_len * (self.latent.entry_width + (ix.head_dim if ix else 0))
            if ly.attn == "latent" else 2 * page_len * kv + page_len // self.sparse.stride * kv
            if ly.attn == "sparse" else 2 * page_len * kv
            for ly in self.layers if ly.attn in kinds)


def window_ring_pages(window: int, chunk: int, page_len: int) -> int:
    """Pages of a row's window ring: the pages a query's window can touch
    (``window / page_len`` and one more when it straddles a boundary), and no
    fewer than one prefill chunk writes at a time."""
    if window % page_len:
        raise ValueError(f"page_len {page_len} must divide the window "
                         f"{window}")
    return max(window // page_len + 1, -(-chunk // page_len))


# ----------------------------------------------------------------- parameters


def _compile_side_by_side(calls) -> None:
    """Compile the programs that the given calls of jitted functions
    (``(jitted, args, kwargs)`` each) will run, all at once; nothing
    executes and no buffer is donated. Each is lowered here, one after
    another, and its lowering handed to a thread of its own to compile: the
    compiler leaves most of a host's cores idle on one program. Lowered in
    threads, the modules' text (and with it the persistent compilation
    cache's key) changed from run to run, and tracing holds the
    interpreter's lock anyway. The calls themselves then find their
    programs compiled: ``jit`` keeps the executable of a lowering it has
    made for the same arguments."""
    from concurrent.futures import ThreadPoolExecutor

    if len(calls) < 2:
        return
    with ThreadPoolExecutor(len(calls)) as pool:
        for done in [pool.submit(fn.lower(*args, **kwargs).compile)
                     for fn, args, kwargs in calls]:
            done.result()


def _normal(key, shape, std, dtype):
    """Drawn in float32, kept in ``dtype``: a bfloat16 draw has 256 values a
    binade."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


#: a whole table in ONE program: drawn op by op, a 261120 x 5120 table held
#: its float32 draw, the scaled copy and the result at once (13.4 GB beside
#: the table before it: the process's peak read 16.0 GB where the engine
#: holds 14.4; my chip runs, PR 37); fused, only the result exists
_normal_table = jax.jit(_normal, static_argnums=(1, 2, 3))


@functools.partial(jax.jit, static_argnames=("spec", "ly"))
def init_layer_params(spec: ModelSpec, ly: LayerSpec, key) -> dict:
    """One layer's parameters from ``key``, in one jitted draw (a whole
    model's experts through float32 at once would not fit beside them); one
    compile per kind of layer."""
    d, dh, dt = spec.d_model, spec.head_dim, jnp.dtype(spec.param_dtype)
    ks = jax.random.split(key, 16)
    s = d ** -0.5
    hq, hk = ly.q_heads * dh, spec.kv_heads * dh
    # a family with fixed multipliers: a projection that a multiplier
    # follows is drawn that much wider, so that every branch reaches the
    # residual stream at order 1 (at N(0, 1/fan_in) a branch behind a
    # multiplier of 0.01-0.09 would vanish under bfloat16 rounding)
    mu = spec.mults or Multipliers()
    lp = {"ln1": jnp.ones((d,), jnp.float32),
          "ln2": jnp.ones((d,), jnp.float32)}
    if ly.attn == "linear":
        lp.update(_init_delta_params(spec, ks[8:15]))
    elif ly.attn == "kda":
        lp.update(_init_kda_params(spec, ks[0:8]))
    elif spec.kda is not None:   # the family's full layer: no norm, a gate
        lp.update(wq=_normal(ks[0], (d, hq), s, dt),
                  wk=_normal(ks[1], (d, hk), s, dt),
                  wv=_normal(ks[2], (d, hk), s, dt),
                  w_g=_normal(ks[3], (d, hq), s, dt),
                  wo=_normal(ks[4], (hq, d), hq ** -0.5, dt))
    elif ly.attn in ("sparse", "lightning"):
        lp.update(_init_sala_params(spec, ly, ks[8:15]))
    elif ly.attn == "conv":
        # [b | c | z] in that order; the taps N(0, 1/taps): the gated
        # product reaches the output projection at order 1
        cs = spec.conv
        lp.update(w_in=_normal(ks[0], (d, 3 * cs.channels), s, dt),
                  conv_w=_normal(ks[1], (cs.taps, cs.channels),
                                 cs.taps ** -0.5, dt),
                  w_out=_normal(ks[4], (cs.channels, d),
                                cs.channels ** -0.5, dt))
    elif spec.delta is not None:   # the family's full layer: QK-norm, no gate
        lp.update(wq=_normal(ks[0], (d, hq), s, dt),
                  wk=_normal(ks[1], (d, hk), s, dt),
                  wv=_normal(ks[2], (d, hk), s, dt),
                  q_norm=jnp.ones((hq,), jnp.float32),
                  k_norm=jnp.ones((hk,), jnp.float32),
                  wo=_normal(ks[4], (hq, d), hq ** -0.5, dt))
    elif ly.ssm:
        lp.update(wq=_normal(ks[0], (d, hq), s, dt),
                  wk=_normal(ks[1], (d, hk), s / mu.key, dt),
                  wv=_normal(ks[2], (d, hk), s, dt),
                  wo=_normal(ks[4], (hq, d),
                             hq ** -0.5 / mu.attention_out, dt),
                  ssm=_init_ssm_params(spec, ks[8:15]))
    elif ly.attn == "latent":
        la, H = spec.latent, ly.q_heads
        lp.update(
            wq_a=_normal(ks[0], (d, la.q_rank), s, dt),
            q_norm=jnp.ones((la.q_rank,), jnp.float32),
            wq_b=_normal(ks[1], (la.q_rank, H * dh), la.q_rank ** -0.5, dt),
            wkv_a=_normal(ks[2], (d, la.entry_dim), s, dt),
            kv_norm=jnp.ones((la.kv_rank,), jnp.float32),
            wkv_b=_normal(ks[3], (la.kv_rank, H * (la.nope_dim + la.v_dim)),
                          la.kv_rank ** -0.5, dt),
            wo=_normal(ks[4], (H * la.v_dim, d), (H * la.v_dim) ** -0.5, dt))
        if la.indexer is not None:   # N(0, 1/fan_in); the norm gain 1, bias 0
            ix = la.indexer
            kx = jax.random.split(jax.random.fold_in(key, 1), 3)
            lp.update(
                ix_wq_b=_normal(kx[0], (la.q_rank, ix.heads * ix.head_dim),
                                la.q_rank ** -0.5, dt),
                ix_wk=_normal(kx[1], (d, ix.head_dim), s, dt),
                ix_w=_normal(kx[2], (d, ix.heads), s, dt),
                ix_k_gain=jnp.ones((ix.head_dim,), jnp.float32),
                ix_k_bias=jnp.zeros((ix.head_dim,), jnp.float32))
    else:
        lp.update(wq=_normal(ks[0], (d, hq), s, dt),
                  wk=_normal(ks[1], (d, hk), s, dt),
                  wv=_normal(ks[2], (d, hk), s, dt),
                  wo=_normal(ks[4], (hq, d), hq ** -0.5, dt))
        if spec.head_gate:
            lp["wgate"] = _normal(ks[3], (d, ly.q_heads), s, dt)
        if spec.qk_norm:
            lp.update(q_norm=jnp.ones((dh,), jnp.float32),
                      k_norm=jnp.ones((dh,), jnp.float32))
    if ly.ffn == "dense":
        f = spec.dense_width
        lp.update(w_gate=_normal(ks[5], (d, f), s / mu.mlp_gate, dt),
                  w_up=_normal(ks[6], (d, f), s, dt),
                  w_down=_normal(ks[7], (f, d), f ** -0.5 / mu.mlp_down, dt))
    else:
        fe, fs, e = spec.expert_width, spec.shared_width, spec.experts_held
        lp["moe"] = {
            "router": jax.random.normal(ks[8], (d, spec.n_experts),
                                        jnp.float32) * s,
            "e_gate": _normal(ks[9], (e, d, fe), s, dt),
            "e_up": _normal(ks[10], (e, d, fe), s, dt),
            "e_down": _normal(ks[11], (e, fe, d), fe ** -0.5, dt)}
        if fs:   # a model without a shared expert holds no such array
            lp["moe"].update(s_gate=_normal(ks[12], (d, fs), s, dt),
                             s_up=_normal(ks[13], (d, fs), s, dt),
                             s_down=_normal(ks[14], (fs, d), fs ** -0.5, dt))
        if spec.scoring == "sigmoid":
            # the family's load-balancing term: small beside the spacing of
            # the top scores (0.013 at 128 experts), so that it changes
            # which expert a near-tie picks and leaves the experts' loads
            # even. At 0.05 it made an expert 2.5 x as popular as its
            # neighbour, and the held experts a step touches differed by 8 %
            # from seed to seed (PERF.md section 6, PR 35)
            lp["moe"]["e_bias"] = 0.005 * jax.random.normal(
                ks[15], (spec.n_experts,), jnp.float32)
    return lp


def _init_ssm_params(spec: ModelSpec, ks) -> dict:
    """A mixer's parameters: the input projection's five segments each drawn
    so that, after ``in_mult`` and its ``mup`` entry, the segment is of
    order 1; the convolution N(0, 1/taps) with a bias N(0, 0.1^2); ``A``
    uniform in [1, 16], ``dt`` log-uniform in [1e-3, 1e-1] (``dt_bias`` its
    inverse softplus) and ``D`` 1, the Mamba-2 defaults: the state then
    remembers tens to hundreds of tokens."""
    sm, d, dt = spec.ssm, spec.d_model, jnp.dtype(spec.param_dtype)
    std_in = d ** -0.5 / (sm.in_mult * jnp.asarray(sm.mup_vector()))
    w_in = (jax.random.normal(ks[0], (d, std_in.shape[0]), jnp.float32)
            * std_in[None, :]).astype(dt)
    step = jnp.exp(jax.random.uniform(
        ks[3], (sm.heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_in": w_in,
        "conv_w": _normal(ks[1], (sm.conv, sm.conv_dim), sm.conv ** -0.5, dt),
        "conv_b": _normal(ks[2], (sm.conv_dim,), 0.1, dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (sm.heads,), jnp.float32,
                                            1.0, 16.0)),
        "D": jnp.ones((sm.heads,), jnp.float32),
        "norm": jnp.ones((sm.d_inner,), jnp.float32),
        "w_out": _normal(ks[5], (sm.d_inner, d),
                         sm.d_inner ** -0.5 / sm.out_mult, dt)}


def _init_delta_params(spec: ModelSpec, ks) -> dict:
    """A delta-rule mixer's parameters, drawn so that a check can see the
    mixer: projections N(0, 1/fan_in) (``[q | k | v]`` as one matrix, the
    channels the convolution runs over); the convolution N(0, 1/taps), no
    bias; ``A`` uniform in [0.5, 1.5] and the step log-uniform in [1e-3,
    1e-1] (``dt_bias`` its inverse softplus) with ``W_a`` a tenth of its
    fan-in's law, so that the decay ``a = exp(-A softplus(W_a u +
    dt_bias))`` spreads over about 0.86 to 0.9995 (a state that remembers
    tens to thousands of tokens); ``W_b`` at 0.3 of its law, so that ``b = 2
    sigmoid(W_b u)`` passes 1 for half the tokens without saturating."""
    ds, d, dt = spec.delta, spec.d_model, jnp.dtype(spec.param_dtype)
    s, H = d ** -0.5, ds.heads
    return {
        **_delta_rule_draws(ds, d, dt, ks, decays=H),
        "w_ab": jnp.concatenate([_normal(ks[1], (d, H), 0.1 * s, dt),
                                 _normal(ks[2], (d, H), 0.3 * s, dt)], axis=1),
        "w_g": _normal(ks[5], (d, H * ds.value_dim), s, dt)}


def _delta_rule_draws(ds: DeltaSpec, d: int, dt, ks, decays: int) -> dict:
    """What a ``linear`` and a ``kda`` mixer draw alike: ``[q | k | v]`` and
    ``wo`` N(0, 1/fan_in), the convolution N(0, 1/taps), ``A`` uniform in
    [0.5, 1.5] a head, the step log-uniform in [1e-3, 1e-1] over ``decays``
    values (a head's one, or one a channel of its key; ``dt_bias`` its
    inverse softplus), the output norm's gain 1."""
    hv = ds.heads * ds.value_dim
    step = jnp.exp(jax.random.uniform(
        ks[3], (decays,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    return {
        "w_qkv": _normal(ks[0], (d, ds.conv_dim), d ** -0.5, dt),
        "conv_w": _normal(ks[6], (ds.conv, ds.conv_dim), ds.conv ** -0.5, dt),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(jax.random.uniform(ks[4], (ds.heads,), jnp.float32,
                                            0.5, 1.5)),
        "o_norm": jnp.ones((ds.value_dim,), jnp.float32),
        "wo": _normal(jax.random.fold_in(ks[0], 1), (hv, d), hv ** -0.5, dt)}


def _init_kda_params(spec: ModelSpec, ks) -> dict:
    """A ``kda`` mixer's parameters, drawn so that a check can see the
    mechanism: projections N(0, 1/fan_in) (``[q | k | v]`` as one matrix, the
    channels the convolution runs over); the convolution N(0, 1/taps), no
    bias. The decay ``a = exp(-A softplus(W_a2 (W_a1 u) + dt_bias))``: ``A``
    uniform in [0.5, 1.5] a HEAD, the step log-uniform in [1e-3, 1e-1] a
    CHANNEL (``dt_bias`` its inverse softplus) and ``W_a2`` at a tenth of its
    fan-in's law, so that the decays spread over about 0.86 to 0.9995 and
    DIFFER across a head's channels (one scalar a head in their place is
    another model). ``W_b`` at 0.3 of its law (``b = 2 sigmoid(W_b u)``
    passes 1 for half the tokens without saturating); the gate's ``W_z2
    (W_z1 u)`` of order 1 with a bias N(0, 0.5^2): a sigmoid away from 0 and
    1."""
    kd, d, dt = spec.kda, spec.d_model, jnp.dtype(spec.param_dtype)
    s, H, r = d ** -0.5, kd.heads, kd.rank
    hk, hv = H * kd.key_dim, H * kd.value_dim
    ka, kz = jax.random.split(ks[1]), jax.random.split(ks[5])
    return {
        **_delta_rule_draws(kd, d, dt, ks, decays=hk),
        "w_a1": _normal(ka[0], (d, r), s, dt),
        "w_a2": _normal(ka[1], (r, hk), 0.1 * r ** -0.5, dt),
        "w_b": _normal(ks[2], (d, H), 0.3 * s, dt),
        "w_z1": _normal(kz[0], (d, r), s, dt),
        "w_z2": _normal(kz[1], (r, hv), r ** -0.5, dt),
        "b_z": 0.5 * jax.random.normal(ks[7], (hv,), jnp.float32)}


#: the QK-norm gain a sparse layer is drawn with: scores ``q . k /
#: sqrt(head_dim)`` of normed queries and keys spread by the product of the
#: two gains, 3 here. At gains of 1 a softmax over tens of thousands of
#: random keys is nearly flat, the attention output nearly zero, and
#: attending densely in the selection's place would pass any check
_SPARSE_QK_GAIN = 3.0 ** 0.5


def _init_sala_params(spec: ModelSpec, ly: LayerSpec, ks) -> dict:
    """A ``sparse`` or a ``lightning`` layer's mixer (the ``minicpm_sala``
    family), drawn so that a check can see the mechanisms: projections N(0,
    1/fan_in), the output projection divided by the branch's multiplier
    (``scale_depth / sqrt(mup_denominator)``) so that the branch reaches the
    stream at order 1; a sparse layer's QK-norm gains :data:`_SPARSE_QK_GAIN`
    (a softmax with a few dozen keys that matter among tens of thousands:
    which blocks are chosen then changes the output), a lightning layer's
    1; the gates ``hidden`` wide on both mixers. A lightning layer's
    ``decay`` (float32, a head) is set by :func:`init_params`, which knows
    the layer's index."""
    d, dh, dt = spec.d_model, spec.head_dim, jnp.dtype(spec.param_dtype)
    s, r = d ** -0.5, spec.mults.attention_out
    if ly.attn == "lightning":
        ls = spec.lightning
        hq = hk = ls.heads * ls.head_dim
        gain, extra = 1.0, {"o_norm": jnp.ones((hq,), jnp.float32),
                            "decay": jnp.ones((ls.heads,), jnp.float32)}
    else:
        hq, hk = ly.q_heads * dh, spec.kv_heads * dh
        gain, extra = _SPARSE_QK_GAIN, {}
    return {"wq": _normal(ks[0], (d, hq), s, dt),
            "wk": _normal(ks[1], (d, hk), s, dt),
            "wv": _normal(ks[2], (d, hk), s, dt),
            "w_g": _normal(ks[3], (d, hq), s, dt),
            "wo": _normal(ks[4], (hq, d), hq ** -0.5 / r, dt),
            "q_norm": jnp.full((dh,), gain, jnp.float32),
            "k_norm": jnp.full((dh,), gain, jnp.float32), **extra}


def init_params(spec: ModelSpec, key) -> dict:
    """Scaled-normal parameters, drawn a layer at a time; embedding and head
    are separate (untied) and hold ``vocab_held`` rows, unless the spec ties
    them (``tied_head``: ONE table, drawn N(0, 1/d) so that the logits it
    gives as the head spread by order 1; a pre-norm block reads the stream
    through a norm, so the small embedding costs nothing). With the family's
    multipliers (:class:`Multipliers`) the embedding reaches the stream at
    order 1 and the logits spread by order 1; so it does (N(0, 1)) in the
    family whose blocks norm their branches' outputs and not their inputs
    (``delta``): each branch joins the stream at order 1 behind its norm."""
    dt = jnp.dtype(spec.param_dtype)
    mu = spec.mults
    ks = jax.random.split(key, spec.n_layers + 2)
    table = (spec.vocab_held, spec.d_model)
    head_std = spec.d_model ** -0.5 / (1.0 if mu is None else mu.lm_head)
    emb_std = head_std if spec.tied_head else (
        1.0 if spec.delta is not None else (
            0.02 if mu is None else 1.0 / mu.embedding))
    p = {"emb": _normal_table(ks[0], table, emb_std, dt),
         "ln_f": jnp.ones((spec.d_model,), jnp.float32)}
    if not spec.tied_head:
        p["head"] = _normal_table(ks[1], table, head_std, dt)
    if not isinstance(ks, jax.core.Tracer):
        _compile_side_by_side([(init_layer_params, (spec, ly, ks[2]), {})
                               for ly in dict.fromkeys(spec.layers)])
    for i in range(spec.n_layers):
        p[f"l{i}"] = init_layer_params(spec, spec.layers[i], ks[2 + i])
        if spec.layers[i].attn == "lightning":
            from ..ops.lightning import lightning_decay

            ls = spec.lightning
            p[f"l{i}"]["decay"] = jnp.asarray(lightning_decay(
                ls.heads, ls.first_layer + i, ls.layers_total))
    return p


def init_kv_pages(spec: ModelSpec, num_pages: int, window_pages: int,
                  page_len: int, compute_dtype: str | None = None,
                  state_slots: int = 0) -> dict:
    """Zeroed slabs, layer -> a tuple of arrays: ``(k, v)``, each
    ``(num_pages, page_len, kv_heads * head_dim)``, for a full layer
    (``(window_pages, ...)`` for a sliding one); ONE array ``(num_pages,
    page_len, entry_width)`` for a latent layer
    (:attr:`LatentSpec.entry_width`). Page 0 of each class is its dummy. A
    token's heads lie side by side in ONE row, so a decode step's entry is
    one contiguous row and a page one contiguous copy, which the decode
    kernel contracts on the MXU as it is: it never slices the page, every
    head meets the whole page in one matmul from a block-diagonal query,
    whatever the heads' count, width (128, or 64: two heads to a lane tile)
    and query rows
    (:func:`~marlin_tpu.ops.paged_attention
    .paged_decode_attention` picks its body by the slab's rank alone;
    held
    ``(page_len, kv_heads, head_dim)``, as the dense model's 16-token pages
    are, a 256-token page cost the kernel 8-9 us a step for 0.6-1.3 us of
    bytes: PERF.md, PR 38). A
    layer with a state-space mixer has two more arrays after its pages,
    indexed by a row's STATE SLOT and not by a page id: the recurrent states
    ``(state_slots, heads, state, head_dim)`` in the mixer's ``state_dtype``
    and the convolution tails ``(state_slots, (conv - 1) * conv_dim / 128``
    rounded up to 8``, 128)`` (a slot whole tiles in one piece, which the
    decode step writes in place; ``(state_slots, conv - 1, conv_dim)`` where
    ``conv_dim`` is not whole lane tiles:
    :func:`~marlin_tpu.ops.ssm.tail_slot_shape`); slot 0
    is the dummy. A ``linear`` (and a ``kda``) layer has those two arrays
    and nothing else (its states ``(state_slots, key_dim, heads * value_dim)``:
    :mod:`~marlin_tpu.ops.delta_rule`); a ``conv`` layer ONE, the tails
    ``(state_slots, taps - 1, channels)``: a layer has the arrays its
    mixer's ``slot_arrays()`` names (a ``lightning`` layer ONE, the states
    ``(state_slots, heads, head_dim, head_dim)``). A ``sparse`` layer has a
    THIRD page-indexed array after K and V: its compressed keys ``(num_pages,
    page_len / stride, kv_heads * head_dim)``, entry ``e`` of page ``p`` the
    mean of the ``2 * stride`` keys that END at token ``p * page_len + (e +
    1) * stride - 1`` (:mod:`~marlin_tpu.ops.sparse_attention`). A latent
    layer with an indexer has a SECOND page-indexed array after its slab:
    its index keys ``(num_pages, page_len, index_head_dim)``, one lane tile
    a token (:mod:`~marlin_tpu.ops.dsa`).
    ``state_slots`` counts every slot of
    the arrays: the rows' and, after them, the pool's snapshot slots."""
    if num_pages < 2 or (spec.has_window and window_pages < 2):
        raise ValueError(f"each page class needs >= 2 pages (page 0 is the "
                         f"dummy), got {num_pages} and {window_pages}")
    if spec.has_state and state_slots < 2:
        raise ValueError(f"a model with recurrent mixers needs >= 2 state "
                         f"slots (slot 0 is the dummy), got {state_slots}")
    dt = jnp.dtype(compute_dtype or spec.compute_dtype)

    def state_arrays(mixer):
        return tuple(jnp.zeros((state_slots, *shape), jnp.dtype(sd or dt))
                     for shape, sd in mixer.slot_arrays())

    def slabs(ly):
        if not ly.owns_pages:   # no page: the state slot's arrays alone
            return state_arrays({"linear": spec.delta, "kda": spec.kda,
                                 "conv": spec.conv,
                                 "lightning": spec.lightning}[ly.attn])
        if ly.attn == "latent":
            ix = spec.latent.indexer
            return (jnp.zeros((num_pages, page_len,
                               spec.latent.entry_width), dt),) + (
                () if ix is None else   # the index keys ride with the page
                (jnp.zeros((num_pages, page_len, ix.head_dim), dt),))
        kv = tuple(
            jnp.zeros((window_pages if ly.attn == "sliding" else num_pages,
                       page_len, spec.kv_heads * spec.head_dim), dt)
            for _ in range(2))
        if ly.attn == "sparse":   # the compressed keys ride with the page
            if page_len % spec.sparse.block:
                raise ValueError(
                    f"page_len {page_len} is not whole blocks of "
                    f"{spec.sparse.block} tokens (sparse_config.block_size)")
            return kv + (jnp.zeros(
                (num_pages, page_len // spec.sparse.stride,
                 spec.kv_heads * spec.head_dim), dt),)
        if not ly.ssm:
            return kv
        return kv + state_arrays(spec.ssm)

    return {f"l{i}": slabs(ly) for i, ly in enumerate(spec.layers)}


# ------------------------------------------------------------ the block, once


def _rmsnorm(x, g, eps: float):
    """Float32 in, float32 out: the caller rounds for its matmuls."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y * g.astype(jnp.float32)


def _mm(a, w, out=None):
    """``a @ w`` with ``w`` in ``a``'s dtype, accumulated in float32 and
    kept so where ``out`` says."""
    return jnp.matmul(a, w.astype(a.dtype),
                      preferred_element_type=jnp.float32).astype(
                          out or a.dtype)


def _rope(x, positions, rope: RopeSpec):
    """Rotate the first ``rotary_dim`` dimensions of every head of ``x``
    (T, heads, head_dim) at ``positions`` (T,); float32 inside."""
    D = rope.rotary_dim
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(rope.inv_freq())[None, :])          # (T, D/2)
    cos = (jnp.cos(ang) * rope.attention_factor)[:, None, :]
    sin = (jnp.sin(ang) * rope.attention_factor)[:, None, :]
    xf = x.astype(jnp.float32)
    if rope.interleave:
        pairs = xf[..., :D].reshape(*xf.shape[:-1], D // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(*xf.shape[:-1], D)
        return jnp.concatenate([turned, xf[..., D:]],
                               axis=-1).astype(x.dtype)
    x1, x2 = xf[..., :D // 2], xf[..., D // 2:D]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xf[..., D:]], axis=-1)
    return out.astype(x.dtype)


def _swiglu(h, w_gate, w_up, w_down, gate_mult=None, down_mult=None):
    """Operands in ``h``'s dtype, the result in float32 (it joins the
    residual stream). With the multipliers of a family that has them, the
    gate's pre-activation (scaled in float32) and the result carry one
    each."""
    if gate_mult is None:
        return _mm(jax.nn.silu(_mm(h, w_gate)) * _mm(h, w_up), w_down,
                   jnp.float32)
    gate = jax.nn.silu(_mm(h, w_gate, jnp.float32) * gate_mult)
    return _mm(gate.astype(h.dtype) * _mm(h, w_up), w_down,
               jnp.float32) * down_mult


def layer_forward(spec: ModelSpec, i: int, lp: dict, x, positions, valid,
                  attend, mix=None):
    """Layer ``i`` over the float32 residual stream ``x`` (T, d) whose rows
    stand at ``positions`` (T,): the one place the block's arithmetic is
    written. ``attend(q, k, v)``
    takes the rotated ``q`` (T, kv_heads, group, head_dim) and this layer's
    new ``k``, ``v`` (T, kv_heads, head_dim), stores them where the program
    keeps its cache, and returns the attention output in ``q``'s shape (a
    latent layer's ``attend`` takes what :func:`_latent_attention` says).
    ``valid`` (T,) marks the rows that are real tokens: the others are routed
    to no expert. Returns ``(x, counts)``; ``counts`` is the expert layer's
    ``(assignments, local assignments, held experts touched)``, zeros for a
    dense FFN. A layer with a state-space mixer takes ``mix`` too
    (:func:`_parallel_mixers`): the recurrent state is handed in and out
    there the way ``attend`` hands in the cache; so does a ``linear``
    layer, which asks ``attend`` for nothing (:func:`_post_norm_layer`,
    :func:`_delta_mixer`), a ``kda`` layer (:func:`_solar_layer`,
    :func:`_kda_mixer`) and a ``conv`` layer (:func:`_short_conv`)."""
    ly = spec.layers[i]
    T, cd = x.shape[0], jnp.dtype(spec.compute_dtype)
    H, kvh, dh = ly.q_heads, spec.kv_heads, spec.head_dim
    if spec.delta is not None:
        return _post_norm_layer(spec, ly, lp, x, attend, mix)
    if spec.lightning is not None:
        return _sala_layer(spec, ly, lp, x, positions, valid, attend, mix)
    if spec.kda is not None:
        return _solar_layer(spec, ly, lp, x, valid, attend, mix)
    if ly.ssm:
        x = _parallel_mixers(spec, ly, lp, x, positions, attend, mix)
        return _ffn_half(spec, ly, lp, x, valid)
    if ly.attn == "latent":
        with jax.named_scope("attn_latent"):
            x = _latent_attention(spec, ly, lp, x, positions, attend)
        return _ffn_half(spec, ly, lp, x, valid)
    if ly.attn == "conv":
        with jax.named_scope("short_conv"):
            x = x + _short_conv(
                spec, lp, _rmsnorm(x, lp["ln1"], spec.norm_eps).astype(cd),
                mix)
        return _ffn_half(spec, ly, lp, x, valid)
    rope = spec.rope_full if ly.attn == "full" else spec.rope_sliding
    with jax.named_scope(f"attn_{ly.attn}"):
        h = _rmsnorm(x, lp["ln1"], spec.norm_eps).astype(cd)
        if spec.qk_norm:
            # each head's queries and keys normed (float32, one gain for
            # all heads) BEFORE the rotation, rounded once after it
            q, k = (_rope(_rmsnorm(_mm(h, lp[w], jnp.float32).reshape(
                T, n, dh), lp[g], spec.norm_eps), positions, rope).astype(cd)
                for w, g, n in (("wq", "q_norm", H), ("wk", "k_norm", kvh)))
        else:
            q = _rope(_mm(h, lp["wq"]).reshape(T, H, dh), positions, rope)
            k = _rope(_mm(h, lp["wk"]).reshape(T, kvh, dh), positions, rope)
        v = _mm(h, lp["wv"]).reshape(T, kvh, dh)
        if spec.head_gate:
            gate = jax.nn.sigmoid(_mm(h, lp["wgate"], jnp.float32))
        o = attend(q.reshape(T, kvh, H // kvh, dh), k, v).reshape(T, H, dh)
        if spec.head_gate:
            o = (o.astype(jnp.float32) * gate[:, :, None]).astype(cd)
        x = x + _mm(o.reshape(T, H * dh).astype(cd), lp["wo"], jnp.float32)
    return _ffn_half(spec, ly, lp, x, valid)


def _sala_layer(spec: ModelSpec, ly: LayerSpec, lp: dict, x, positions,
                valid, attend, mix):
    """The ``minicpm_sala`` family's block: pre-norm, ``x = x + r
    mixer(rmsnorm(x))`` then ``x = x + r swiglu(rmsnorm(x))`` with ``r =
    scale_depth / sqrt(mup_denominator)`` (:class:`Multipliers`:
    ``attention_out`` and ``mlp_down``). The mixer of a ``sparse`` layer is
    :func:`_gated_attention` under the ``attn_sparse`` scope, of a
    ``lightning`` layer :func:`_lightning_mixer` under ``lightning_attn``."""
    u = _rmsnorm(x, lp["ln1"], spec.norm_eps).astype(spec.compute_dtype)
    if ly.attn == "lightning":
        with jax.named_scope("lightning_attn"):
            y = _lightning_mixer(spec, lp, u, positions, mix)
    else:
        with jax.named_scope("attn_sparse"):
            y = _gated_attention(spec, ly, lp, u, attend)
    return _ffn_half(spec, ly, lp, x + spec.mults.attention_out * y, valid)


def _head_norm(x, g, eps: float, heads: int):
    """The family's QK-norm: ``x`` (T, heads * dh) float32 cut into heads,
    each normed over its ``dh`` values with the one gain ``g`` (dh,)."""
    return _rmsnorm(x.reshape(x.shape[0], heads, -1), g, eps)


def _gated_attention(spec: ModelSpec, ly: LayerSpec, lp: dict, u, attend):
    """GQA attention with NO positional term and an elementwise output gate
    over the normed input ``u`` (T, d) in the compute dtype (the
    ``minicpm4`` mixer of a ``sparse`` layer, the ``solar_open2`` family's
    ``full`` layer): GQA projections, per-head QK-norm where the spec has
    one; ``attend(q (T, kv_heads, group, head_dim), k, v (T, kv_heads,
    head_dim))`` stores the keys and the values (a sparse layer's: the keys'
    compressed form too) where the program keeps its cache and attends (a
    sparse layer's: densely or over the selected blocks by each query's
    position); then the elementwise sigmoid gate ``sigmoid(W_g u)`` and
    ``wo``."""
    cd = u.dtype
    T, H, kvh, dh = u.shape[0], ly.q_heads, spec.kv_heads, spec.head_dim
    if spec.qk_norm:
        q = _head_norm(_mm(u, lp["wq"], jnp.float32), lp["q_norm"],
                       spec.norm_eps, H).astype(cd)
        k = _head_norm(_mm(u, lp["wk"], jnp.float32), lp["k_norm"],
                       spec.norm_eps, kvh).astype(cd)
    else:
        q = _mm(u, lp["wq"]).reshape(T, H, dh)
        k = _mm(u, lp["wk"]).reshape(T, kvh, dh)
    v = _mm(u, lp["wv"]).reshape(T, kvh, dh)
    gate = jax.nn.sigmoid(_mm(u, lp["w_g"], jnp.float32))
    o = attend(q.reshape(T, kvh, H // kvh, dh), k, v)
    return _mm((o.reshape(T, H * dh).astype(jnp.float32) * gate).astype(cd),
               lp["wo"], jnp.float32)


def _lightning_mixer(spec: ModelSpec, lp: dict, u, positions, mix):
    """The ``lightning-attn`` mixer over the normed input ``u`` (T, d) in
    the compute dtype: projections, per-head QK-norm, the rotary embedding
    over the whole head on queries and keys, ``q / sqrt(head_dim)``;
    ``mix(q, k, v (T, heads, head_dim), lp)`` runs the recurrence ``S =
    lambda S + k v^T; o = S^T q`` where the program keeps the row's state
    and returns ``o`` (T, heads, head_dim) float32; then an RMSNorm over ALL
    ``heads * head_dim`` columns, the elementwise sigmoid gate, ``wo``."""
    ls, cd = spec.lightning, u.dtype
    T, H, K = u.shape[0], ls.heads, ls.head_dim
    q, k = (_rope(_head_norm(_mm(u, lp[w], jnp.float32), lp[g],
                             spec.norm_eps, H), positions, spec.rope_full)
            for w, g in (("wq", "q_norm"), ("wk", "k_norm")))
    o = mix((q * K ** -0.5).astype(cd), k.astype(cd),
            _mm(u, lp["wv"]).reshape(T, H, K), lp)
    o = _rmsnorm(o.reshape(T, H * K), lp["o_norm"], spec.norm_eps)
    gate = jax.nn.sigmoid(_mm(u, lp["w_g"], jnp.float32))
    return _mm((o * gate).astype(cd), lp["wo"], jnp.float32)


def _short_conv(spec: ModelSpec, lp: dict, u, mix):
    """The gated short convolution over the normed input ``u`` (T, d) in the
    compute dtype: ``[b | c | z] = u W_in`` (thirds, in that order, float32);
    ``s = b * z``; ``mix(s (T, channels), lp)`` runs the causal depthwise
    convolution where the program keeps the row's tail and returns it in
    float32 (no bias, no activation); ``y = (c * conv(s)) W_out``."""
    ch, cd = spec.conv.channels, u.dtype
    p = _mm(u, lp["w_in"], jnp.float32)
    b, c, z = p[:, :ch], p[:, ch:2 * ch], p[:, 2 * ch:]
    return _mm((c * mix((b * z).astype(cd), lp)).astype(cd), lp["w_out"],
               jnp.float32)


def _latent_attention(spec: ModelSpec, ly: LayerSpec, lp: dict, x, positions,
                      attend):
    """The attention half of a latent layer: queries through their own
    latent (``wq_a``, norm, ``wq_b``), the cache entry ``(c_kv, k_pe)`` from
    ``wkv_a`` (the norm on ``c_kv`` only), the rotary embedding on the
    ``rope_dim`` columns of each, then ``attend(q_nope (T, H, nope), q_pe
    (T, H, rope), entry (T, entry_width), scale (T,) float32, wkv_b
    (kv_rank, H, nope + v))``, which stores ``entry`` where the program keeps
    its cache and returns the heads' outputs (T, H, v): every score is
    ``scale[i] * (q_nope . k_nope + q_pe . k_pe)``. A layer with an indexer
    hands ``attend`` one argument more, under the ``dsa_index`` scope: the
    index queries (T, J, D), the token's index key (T, D) (what the second
    array keeps) and the heads' weights (T, J) float32
    (:func:`_index_operands`)."""
    la, cd = spec.latent, jnp.dtype(spec.compute_dtype)
    T, H = x.shape[0], ly.q_heads
    h = _rmsnorm(x, lp["ln1"], spec.norm_eps).astype(cd)
    c_q = _rmsnorm(_mm(h, lp["wq_a"], jnp.float32), lp["q_norm"],
                   spec.norm_eps).astype(cd)
    index = ()
    if la.indexer is not None:
        with jax.named_scope("dsa_index"):
            index = (_index_operands(spec, lp, h, c_q, positions),)
    q = _mm(c_q, lp["wq_b"]).reshape(T, H, la.nope_dim + la.rope_dim)
    q_pe = _rope(q[..., la.nope_dim:], positions, spec.rope_full)
    kv = _mm(h, lp["wkv_a"], jnp.float32)
    c_kv = _rmsnorm(kv[:, :la.kv_rank], lp["kv_norm"], spec.norm_eps)
    k_pe = _rope(kv[:, None, la.kv_rank:], positions, spec.rope_full)[:, 0]
    entry = jnp.concatenate(
        [c_kv, k_pe, jnp.zeros((T, la.entry_width - la.entry_dim))],
        axis=-1).astype(cd)
    o = attend(q[..., :la.nope_dim], q_pe, entry,
               la.query_scale(positions),
               lp["wkv_b"].reshape(la.kv_rank, H, la.nope_dim + la.v_dim),
               *index)
    return x + _mm(o.reshape(T, H * la.v_dim).astype(cd), lp["wo"],
                   jnp.float32)


def _index_operands(spec: ModelSpec, lp: dict, h, c_q, positions):
    """A token's side of the lightning indexer (:mod:`~marlin_tpu.ops.dsa`):
    ``q^I`` (T, J, D) from the query latent ``c_q``, ``k^I`` (T, D) =
    LayerNorm(``h`` W_k) (gain and bias, float32), both with their first
    ``rope_dim`` columns rotated by the layer's frequencies in the
    ROTATE-HALF layout whatever the main attention's is, in the compute
    dtype; ``w`` (T, J) float32 = ``h`` W_w x J^-1/2 x D^-1/2."""
    ix, cd = spec.latent.indexer, h.dtype
    T = h.shape[0]
    rope = dataclasses.replace(spec.rope_full, interleave=False)
    qi = _rope(_mm(c_q, lp["ix_wq_b"]).reshape(T, ix.heads, ix.head_dim),
               positions, rope)
    k = _mm(h, lp["ix_wk"], jnp.float32)
    mean = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(k - mean), axis=-1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(var + ix.eps) * lp["ix_k_gain"]
         + lp["ix_k_bias"])
    ki = _rope(k[:, None, :], positions, rope)[:, 0].astype(cd)
    w = _mm(h, lp["ix_w"], jnp.float32) * (ix.heads * ix.head_dim) ** -0.5
    return qi, ki, w


def _attend_selected_tokens(q, ctx, scores, n_valid, la: LatentSpec,
                            tile: int, entry_of=None, live=None):
    """The selection and the attention of rows that hold their index scores:
    ``q`` (R, H, entry_width) absorbed and scaled, ``scores`` (R, L) float32
    over positions 0..L-1 of which row ``r``'s first ``n_valid[r]`` count.
    ``ctx`` is what a list of positions reads entries from: the row's context
    (L, entry_width) in position order, or with ``entry_of`` (positions (R,
    k) -> flat rows of ``ctx``) a slab's rows. A ``tile`` of rows at a time:
    under ``dsa_select`` the k-th score, the mask and the tile's lists; under
    ``dsa_attend`` the tile's gathered entries (``tile x topk x
    entry_width``: never the whole chunk's) and one softmax over each row's
    own. A tile none of whose rows is ``live`` (a short chunk's padding) is
    not computed and reads zeros. Returns the attended latents (R, H,
    kv_rank) and, a row, the entries it attended (R,) int32: the lists'
    own lengths, 0 in a tile that was not computed."""
    from ..ops import dsa

    R, L = scores.shape
    pad = -L % 128
    if pad:   # the prefix counts go by whole lane tiles
        scores = jnp.pad(scores, ((0, 0), (0, pad)),
                         constant_values=-jnp.inf)
    k = min(la.indexer.topk, L + pad)
    tile = min(tile, R)
    if R % tile:
        raise ValueError(f"{R} rows are not whole tiles of {tile}")

    def one_tile(args):
        qt, st, nt, at, lt = args

        def attended():
            with jax.named_scope("dsa_select"):
                idx, count = dsa.select_tokens(st, nt, k)
            with jax.named_scope("dsa_attend"):
                rows = idx if entry_of is None else entry_of(idx, at)
                return dsa.attend_list(qt, dsa.gather_entries(ctx, rows),
                                       count, la.kv_rank), count

        if live is None:
            return attended()
        return jax.lax.cond(
            jnp.any(lt), attended,
            lambda: (jnp.zeros((tile, q.shape[1], la.kv_rank), q.dtype),
                     jnp.zeros((tile,), jnp.int32)))

    tiles = lambda a: a.reshape(R // tile, tile, *a.shape[1:])  # noqa: E731
    out, count = jax.lax.map(one_tile, (
        tiles(q), tiles(scores), tiles(n_valid), tiles(jnp.arange(R)),
        tiles(jnp.ones((R,), bool) if live is None else live)))
    return out.reshape(R, *out.shape[2:]), count.reshape(R)


def _parallel_mixers(spec: ModelSpec, ly: LayerSpec, lp: dict, x, positions,
                     attend, mix):
    """The first half of a layer with a state-space mixer: ``u =
    rmsnorm(x)`` feeds BOTH mixers, whose outputs are added to the stream.
    Attention: ``q``, ``k`` (times ``key``), ``v`` from ``u *
    attention_in``, the rotary embedding, ``attend`` as a full layer's, ``wo``,
    times ``attention_out``; no head gate. The mixer (:func:`_ssm_mixer`)
    under the ``ssm_mixer`` scope."""
    mu, cd = spec.mults or Multipliers(), jnp.dtype(spec.compute_dtype)
    T, H, kvh, dh = x.shape[0], ly.q_heads, spec.kv_heads, spec.head_dim
    u = _rmsnorm(x, lp["ln1"], spec.norm_eps)
    with jax.named_scope("attn_full"):
        h = (u * mu.attention_in).astype(cd)
        q = _rope(_mm(h, lp["wq"]).reshape(T, H, dh), positions,
                  spec.rope_full)
        k = (_mm(h, lp["wk"], jnp.float32) * mu.key).astype(cd)
        k = _rope(k.reshape(T, kvh, dh), positions, spec.rope_full)
        v = _mm(h, lp["wv"]).reshape(T, kvh, dh)
        o = attend(q.reshape(T, kvh, H // kvh, dh), k, v)
        a = _mm(o.reshape(T, H * dh).astype(cd), lp["wo"],
                jnp.float32) * mu.attention_out
    with jax.named_scope("ssm_mixer"):
        m = _ssm_mixer(spec, lp["ssm"], u, mix)
    return x + a + m


def _scan_operands(sm: SsmSpec, conv, cd):
    """From the convolution's output (T, conv_dim) float32 to the
    recurrence's operands: SiLU, then ``x`` (T, heads, head_dim), ``B``, ``C``
    (T, groups, state), in ``cd``."""
    act = jax.nn.silu(conv).astype(cd)
    gn, T = sm.groups * sm.state, conv.shape[0]
    return (act[:, :sm.d_inner].reshape(T, sm.heads, sm.head_dim),
            act[:, sm.d_inner:sm.d_inner + gn].reshape(T, sm.groups,
                                                       sm.state),
            act[:, sm.d_inner + gn:].reshape(T, sm.groups, sm.state))


def _ssm_mixer(spec: ModelSpec, sp: dict, u, mix):
    """The Mamba-2 mixer over the normed input ``u`` (T, d) float32: ``p =
    (w_in (u * in_mult)) * mup`` cut into gate ``z``, ``[x | B | C]`` and
    ``dt``; ``dt = softplus(dt + dt_bias)``; ``mix(xbc (T, conv_dim), dt (T,
    heads) float32, sp)`` runs the causal convolution, SiLU and the
    recurrence where the program keeps the row's state and returns ``y`` (T,
    d_inner) float32 (``D x`` included); then the gate and the grouped
    RMSNorm (gate first unless ``norm_before_gate``), ``w_out``, times
    ``out_mult``."""
    sm, cd = spec.ssm, jnp.dtype(spec.compute_dtype)
    T, di = u.shape[0], sm.d_inner
    p = _mm((u * sm.in_mult).astype(cd), sp["w_in"], jnp.float32) \
        * jnp.asarray(sm.mup_vector())
    z, xbc, dt = p[:, :di], p[:, di:di + sm.conv_dim], p[:, di + sm.conv_dim:]
    dt = jax.nn.softplus(dt + sp["dt_bias"])
    y = mix(xbc.astype(cd), dt, sp)

    def norm(v):  # over each of the `groups` groups of d_inner / groups
        vg = v.reshape(T, sm.groups, di // sm.groups)
        vg = vg * jax.lax.rsqrt(jnp.mean(vg * vg, axis=-1, keepdims=True)
                                + spec.norm_eps)
        return vg.reshape(T, di) * sp["norm"]

    gate = jax.nn.silu(z)
    if not sm.rms_norm:
        y = y * gate
    elif sm.norm_before_gate:
        y = norm(y) * gate
    else:
        y = norm(y * gate)
    return _mm(y.astype(cd), sp["w_out"], jnp.float32) * sm.out_mult


def _post_norm_layer(spec: ModelSpec, ly: LayerSpec, lp: dict, x, attend,
                     mix):
    """The ``olmo_hybrid`` family's block: no norm on a branch's input, one
    on its OUTPUT: ``x = x + rmsnorm(mixer(x))``, then ``x = x +
    rmsnorm(swiglu(x))``. The mixer of a ``linear`` layer is the delta rule
    (:func:`_delta_mixer`, under the ``linear_attn`` scope); of a ``full``
    layer, attention whose queries and keys pass an RMSNorm over the WHOLE
    projection (a gain a column) before they are cut into heads, with no
    rotary embedding and no head gate."""
    cd = jnp.dtype(spec.compute_dtype)
    T, H, kvh, dh = x.shape[0], ly.q_heads, spec.kv_heads, spec.head_dim
    u = x.astype(cd)
    if ly.attn == "linear":
        with jax.named_scope("linear_attn"):
            y = _delta_mixer(spec, lp, u, mix)
    else:
        with jax.named_scope("attn_full"):
            q = _rmsnorm(_mm(u, lp["wq"], jnp.float32), lp["q_norm"],
                         spec.norm_eps).astype(cd)
            k = _rmsnorm(_mm(u, lp["wk"], jnp.float32), lp["k_norm"],
                         spec.norm_eps).astype(cd)
            v = _mm(u, lp["wv"])
            o = attend(q.reshape(T, kvh, H // kvh, dh), k.reshape(T, kvh, dh),
                       v.reshape(T, kvh, dh))
            y = _mm(o.reshape(T, H * dh).astype(cd), lp["wo"], jnp.float32)
    x = x + _rmsnorm(y, lp["ln1"], spec.norm_eps)
    with jax.named_scope("ffn_dense"):
        y = _swiglu(x.astype(cd), lp["w_gate"], lp["w_up"], lp["w_down"])
    return x + _rmsnorm(y, lp["ln2"], spec.norm_eps), \
        jnp.zeros((3,), jnp.int32)


def _delta_operands(ds: DeltaSpec, conv, cd):
    """From the convolution's output (T, conv_dim) float32 to the delta
    rule's operands: SiLU, then per head ``q = q~ / |q~| * key_dim^-1/2``,
    ``k = k~ / |k~|`` (the lengths in float32, 1e-6 under the root) and
    ``v``, in ``cd``: ``q``, ``k`` (T, heads, key_dim), ``v`` (T, heads,
    value_dim)."""
    act = jax.nn.silu(conv)
    T, H, K, V = conv.shape[0], ds.heads, ds.key_dim, ds.value_dim

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                                 + 1e-6)

    q = unit(act[:, :H * K].reshape(T, H, K)) * K ** -0.5
    k = unit(act[:, H * K:2 * H * K].reshape(T, H, K))
    return q.astype(cd), k.astype(cd), \
        act[:, 2 * H * K:].reshape(T, H, V).astype(cd)


def _delta_mixer(spec: ModelSpec, lp: dict, u, mix):
    """The gated delta-rule mixer over the branch input ``u`` (T, d) in the
    compute dtype: ``[q~ | k~ | v~] = w_qkv u``; the log-decay ``g = -exp(
    A_log) softplus(W_a u + dt_bias)`` and the step ``b = sigmoid(W_b u)``
    (doubled with ``neg_eigval``), one a head, float32; ``mix(qkv (T,
    conv_dim), g, b (T, heads) float32, lp)`` runs the causal convolution,
    SiLU, the lengths (:func:`_delta_operands`) and the recurrence where the
    program keeps the row's state and returns ``o`` (T, heads, value_dim)
    float32; then an RMSNorm over each head's ``value_dim`` values (one gain
    vector for all heads), the output gate ``silu(w_g u)``, ``wo``."""
    ds, cd = spec.delta, jnp.dtype(spec.compute_dtype)
    T, H, V = u.shape[0], ds.heads, ds.value_dim
    ab = _mm(u, lp["w_ab"], jnp.float32)
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ab[:, :H] + lp["dt_bias"])
    beta = jax.nn.sigmoid(ab[:, H:]) * (2.0 if ds.neg_eigval else 1.0)
    o = mix(_mm(u, lp["w_qkv"]), g, beta, lp)
    o = _rmsnorm(o, lp["o_norm"], spec.norm_eps)
    gate = jax.nn.silu(_mm(u, lp["w_g"], jnp.float32)).reshape(T, H, V)
    return _mm((o * gate).reshape(T, H * V).astype(cd), lp["wo"],
               jnp.float32)


def _solar_layer(spec: ModelSpec, ly: LayerSpec, lp: dict, x, valid, attend,
                 mix):
    """The ``solar_open2`` family's block: pre-norm, ``x = x +
    mixer(rmsnorm(x))`` then the expert layer (:func:`_ffn_half`). The
    mixer of a ``kda`` layer is :func:`_kda_mixer` under the ``attn_kda``
    scope, of a ``full`` layer :func:`_gated_attention` under
    ``attn_full``."""
    u = _rmsnorm(x, lp["ln1"], spec.norm_eps).astype(spec.compute_dtype)
    if ly.attn == "kda":
        with jax.named_scope("attn_kda"):
            y = _kda_mixer(spec, lp, u, mix)
    else:
        with jax.named_scope("attn_full"):
            y = _gated_attention(spec, ly, lp, u, attend)
    return _ffn_half(spec, ly, lp, x + y, valid)


def _kda_mixer(spec: ModelSpec, lp: dict, u, mix):
    """The delta-rule mixer with a decay a channel over the normed input
    ``u`` (T, d) in the compute dtype: ``[q~ | k~ | v~] = w_qkv u``; the
    log-decay ``g = -exp(A_log) softplus(W_a2 (W_a1 u) + dt_bias)``, ``(T,
    heads, key_dim)`` float32 (``A_log`` a head, ``dt_bias`` a channel), and
    the step ``b = sigmoid(W_b u)`` a head (doubled with ``neg_eigval``);
    ``mix(qkv (T, conv_dim), g, b, lp)`` runs the causal convolution, SiLU,
    the lengths (:func:`_delta_operands`) and the recurrence where the
    program keeps the row's state and returns ``o`` (T, heads, value_dim)
    float32; then an RMSNorm over each head's values (one gain vector for
    all heads), the low-rank gate ``sigmoid(W_z2 (W_z1 u) + b_z)``,
    ``wo``."""
    kd, cd = spec.kda, u.dtype
    T, H, K, V = u.shape[0], kd.heads, kd.key_dim, kd.value_dim
    g = -jnp.exp(lp["A_log"])[None, :, None] * jax.nn.softplus(
        (_mm(_mm(u, lp["w_a1"]), lp["w_a2"], jnp.float32)
         + lp["dt_bias"]).reshape(T, H, K))
    beta = jax.nn.sigmoid(_mm(u, lp["w_b"], jnp.float32)) * (
        2.0 if kd.neg_eigval else 1.0)
    o = mix(_mm(u, lp["w_qkv"]), g, beta, lp)
    o = _rmsnorm(o, lp["o_norm"], spec.norm_eps)
    gate = jax.nn.sigmoid(_mm(_mm(u, lp["w_z1"]), lp["w_z2"], jnp.float32)
                          + lp["b_z"]).reshape(T, H, V)
    return _mm((o * gate).reshape(T, H * V).astype(cd), lp["wo"],
               jnp.float32)


def _ffn_half(spec: ModelSpec, ly: LayerSpec, lp: dict, x, valid):
    """The FFN half of :func:`layer_forward`, every attention kind's."""
    cd = jnp.dtype(spec.compute_dtype)
    h = _rmsnorm(x, lp["ln2"], spec.norm_eps)
    if ly.ffn == "dense":
        mu = spec.mults
        with jax.named_scope("ffn_dense"):
            out = _swiglu(h.astype(cd), lp["w_gate"], lp["w_up"],
                          lp["w_down"], *(() if mu is None else
                                          (mu.mlp_gate, mu.mlp_down)))
            return x + out, jnp.zeros((3,), jnp.int32)
    from .moe import moe_experts_ffn

    with jax.named_scope("moe_experts"):
        out, counts = moe_experts_ffn(
            lp["moe"], h, valid, top_k=spec.top_k,
            first_expert=spec.first_expert, routed_scale=spec.routed_scale,
            compute_dtype=cd, scoring=spec.scoring,
            renorm_eps=spec.renorm_eps, n_group=spec.n_group,
            topk_group=spec.topk_group)
    return x + out, counts


def _head_logits(spec: ModelSpec, params: dict, x):
    """Float32 logits over the held rows of the head: its own table, or
    the embedding itself where the spec ties them."""
    xf = _rmsnorm(x, params["ln_f"], spec.norm_eps).astype(
        spec.compute_dtype)
    head = params["emb" if spec.tied_head else "head"]
    logits = jnp.matmul(xf, head.astype(xf.dtype).T,
                        preferred_element_type=jnp.float32)
    return logits if spec.mults is None else logits * spec.mults.lm_head


def _embed(spec: ModelSpec, params: dict, tokens):
    """The residual stream's first value, float32."""
    x = params["emb"][tokens].astype(jnp.float32)
    return x if spec.mults is None else x * spec.mults.embedding


def _attend_dense(q, k, v, q_pos, k_pos, k_live, window, block=None):
    """Masked softmax attention of ``q`` (T, kvh, g, dh) over ``k``/``v``
    (L, kvh, dh), one KV head at a time (the scores of all heads of a long
    bucket at once would be gigabytes): key ``j`` is visible to query ``i``
    iff it is live, not ahead of it and, with a ``window``, less than
    ``window`` behind. With ``block`` the keys (in ascending position) are
    met ``block`` at a time with a running softmax, and a block that begins
    after the last query is skipped: a chunk early in a long bucket's table
    pays for the positions before it, not for the table."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    T, L = q.shape[0], k.shape[0]

    def seen(pos, live):
        ok = live[None, :] & (pos[None, :] <= q_pos[:, None])
        if window is not None:
            ok &= q_pos[:, None] - pos[None, :] < window
        return ok

    def scores(qh, kh, pos, live):
        s = jnp.einsum("pgd,td->gpt", qh, kh,
                       preferred_element_type=jnp.float32) * scale
        return jnp.where(seen(pos, live)[None], s, _MASKED)

    if block is None or L <= block:
        def one(qkv):
            qh, kh, vh = qkv                   # (T, g, dh), (L, dh), (L, dh)
            p = jax.nn.softmax(scores(qh, kh, k_pos, k_live), axis=-1)
            return jnp.einsum("gpt,td->pgd", p.astype(qh.dtype), vh)
    else:
        nb = -(-L // block)
        pad = nb * block - L

        def blocks(x, fill=0):
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                        constant_values=fill)
            return x.reshape(nb, block, *x.shape[1:])

        pos_b, live_b = blocks(k_pos), blocks(k_live, False)

        def one(qkv):
            qh, kh, vh = qkv
            g = qh.shape[1]

            def step(carry, blk):
                kb, vb, pb, lb = blk

                def meet(carry):
                    m, l, acc = carry
                    s = scores(qh, kb, pb, lb)             # (g, T, block)
                    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
                    alpha = jnp.exp(m - m_new)
                    p = jnp.exp(s - m_new[..., None])
                    pv = jnp.einsum("gpt,td->gpd", p.astype(qh.dtype), vb,
                                    preferred_element_type=jnp.float32)
                    return (m_new, alpha * l + jnp.sum(p, axis=-1),
                            acc * alpha[..., None] + pv)

                return jax.lax.cond(pb[0] <= q_pos[-1], meet,
                                    lambda c: c, carry), None

            init = (jnp.full((g, T), _MASKED, jnp.float32),
                    jnp.zeros((g, T), jnp.float32),
                    jnp.zeros((g, T, qh.shape[-1]), jnp.float32))
            (_, l, acc), _ = jax.lax.scan(
                step, init, (blocks(kh), blocks(vh), pos_b, live_b))
            return (acc / l[..., None]).transpose(1, 0, 2).astype(qh.dtype)

    o = jax.lax.map(one, (q.transpose(1, 0, 2, 3), k.transpose(1, 0, 2),
                          v.transpose(1, 0, 2)))
    return o.transpose(1, 0, 2, 3)


def _attend_latent_blocks(q_nope, q_pe, ctx, wkv_b, scale, q_pos,
                          la: LatentSpec, block: int):
    """Prefill's latent attention, unabsorbed: the queries ``q_nope`` (T, H,
    nope), ``q_pe`` (T, H, rope) at ``q_pos`` against the gathered entries
    ``ctx`` (L, entry_width), entry ``j`` at position ``j``, visible to
    query ``i`` iff ``j <= q_pos[i]``. The entries are met ``block`` at a
    time with a running softmax, all heads at once: each block's latents
    are up-projected to per-head keys and values there (``wkv_b`` (kv_rank,
    H, nope + v)), so the whole table's keys and values never exist, and a
    block that begins after the last query is skipped whole. Returns (T, H,
    v) in the queries' dtype."""
    T, H, n = q_nope.shape
    cd = q_nope.dtype
    nb = -(-ctx.shape[0] // block)
    blocks = jnp.pad(ctx, [(0, nb * block - ctx.shape[0]), (0, 0)]).reshape(
        nb, block, ctx.shape[1])
    w = wkv_b.astype(cd)

    def step(carry, blk):
        cb, b0 = blk

        def meet(carry):
            m, l, acc = carry
            kv = jnp.einsum("sc,chd->shd", cb[:, :la.kv_rank], w,
                            preferred_element_type=jnp.float32).astype(cd)
            s = (jnp.einsum("thd,shd->hts", q_nope, kv[..., :n],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("thr,sr->hts", q_pe,
                              cb[:, la.kv_rank:la.entry_dim],
                              preferred_element_type=jnp.float32))
            seen = (b0 + jnp.arange(block))[None, :] <= q_pos[:, None]
            s = jnp.where(seen[None], s * scale[None, :, None], _MASKED)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            pv = jnp.einsum("hts,shd->htd", p.astype(cd), kv[..., n:],
                            preferred_element_type=jnp.float32)
            return (m_new, alpha * l + jnp.sum(p, axis=-1),
                    acc * alpha[..., None] + pv)

        return jax.lax.cond(b0 <= q_pos[-1], meet, lambda c: c, carry), None

    init = (jnp.full((H, T), _MASKED, jnp.float32),
            jnp.zeros((H, T), jnp.float32),
            jnp.zeros((H, T, la.v_dim), jnp.float32))
    (_, l, acc), _ = jax.lax.scan(
        step, init, (blocks, jnp.arange(nb) * block))
    return (acc / l[..., None]).transpose(1, 0, 2).astype(cd)


@functools.partial(jax.jit, static_argnames=("scale", "bq", "bkv"))
def _latent_prefill_flash_head(qh, kh, vh, q_offset, scale: float, bq: int,
                               bkv: int):
    """One head of :func:`_latent_prefill_flash_call`: the flash panel
    kernel from an empty state, normalised. Jitted by itself because the
    kernel's operation in a trace takes the innermost jitted name."""
    from ..ops.flash_attention import flash_attention_panel

    T, d = qh.shape
    m, l, acc = flash_attention_panel(
        qh, kh, vh, jnp.full((T,), _MASKED, jnp.float32),
        jnp.zeros((T,), jnp.float32), jnp.zeros((T, d), jnp.float32),
        q_offset, 0, q_offset + T, causal=True, scale=scale, bq=bq, bkv=bkv)
    return acc / jnp.maximum(l, 1e-30)[:, None]


@functools.partial(jax.jit, static_argnames=("scale",))
def _latent_prefill_flash_call(q, k, v, q_offset, scale: float):
    """Causal attention of a chunk's queries ``q`` (H, T, d), query ``i`` at
    position ``q_offset + i``, over per-head keys and values ``k``, ``v`` (H,
    L, d) at positions 0..L-1: the flash panel kernel
    (:func:`~marlin_tpu.ops.flash_attention.flash_attention_panel`: score
    tiles stay in VMEM, a key block wholly after the chunk is skipped)
    vmapped over heads."""
    from ..ops.flash_attention import block_divisor

    head = functools.partial(_latent_prefill_flash_head, scale=scale,
                             bq=block_divisor(q.shape[1]),
                             bkv=min(1024, k.shape[1]))
    return jax.vmap(head, in_axes=(0, 0, 0, None))(q, k, v, q_offset)


def _attend_latent_flash(q_nope, q_pe, ctx, wkv_b, scale, chunk_start,
                         la: LatentSpec):
    """Prefill's latent attention on the chip, unabsorbed, through the flash
    kernel: the gathered entries ``ctx`` (L, entry_width) are up-projected to
    per-head keys ``[k_nope_h | k_pe]`` and values once (0.3 GB a layer at
    19k positions, transient), the position-dependent part of ``scale``
    goes into the queries, and the chunk (T a multiple of 128, its first
    query at ``chunk_start``) attends causally. What
    :func:`_attend_latent_blocks` computes with its scores in HBM (ten
    passes over 32 x 1024 x 1024 floats a key block: 17 ms a layer at 17k
    positions where the matmuls are 1.5) this keeps in VMEM."""
    T, H, n = q_nope.shape
    cd = q_nope.dtype
    L = ctx.shape[0]
    bkv = 1024 if L >= 1024 else -(-L // 128) * 128
    # a no-op where the caller gathered whole key blocks (flash_table_pages)
    ctx = jnp.pad(ctx, [(0, -(-L // bkv) * bkv - L), (0, 0)])
    w = wkv_b.astype(cd)
    # the keys [k_nope_h | k_pe] as ONE product of the stored entry: the
    # latent columns through the key half of W_kvb, the rotary columns
    # through an identity, the pad columns through zeros. Slicing and
    # joining the per-head arrays instead moved 0.5 GB a layer
    r = la.rope_dim
    w_key = jnp.zeros((ctx.shape[1], H, n + r), cd)
    w_key = w_key.at[:la.kv_rank, :, :n].set(w[..., :n])
    w_key = w_key.at[la.kv_rank:la.entry_dim, :, n:].set(
        jnp.broadcast_to(jnp.eye(r, dtype=cd)[:, None, :], (r, H, r)))
    k = jnp.einsum("se,ehd->hsd", ctx, w_key,
                   preferred_element_type=jnp.float32).astype(cd)
    v = jnp.einsum("sc,chd->hsd", ctx[:, :la.kv_rank], w[..., n:],
                   preferred_element_type=jnp.float32).astype(cd)
    tau = (scale / la.softmax_scale)[:, None, None]
    q = (jnp.concatenate([q_nope, q_pe], axis=-1).astype(jnp.float32)
         * tau).astype(cd).transpose(1, 0, 2)
    d = max(k.shape[-1], la.v_dim)   # the kernel has one head size

    def widen(x):
        return jnp.pad(x, [(0, 0), (0, 0), (0, d - x.shape[-1])])

    o = _latent_prefill_flash_call(widen(q), widen(k), widen(v),
                                   chunk_start, scale=la.softmax_scale)
    return o[..., :la.v_dim].transpose(1, 0, 2).astype(cd)


def flash_table_pages(pages: int, page_len: int) -> int:
    """How many pages of a row's table prefill gathers for a latent layer:
    the table's own, rounded up to whole key blocks of the flash kernel
    (1024 positions) where it spans one; the extra entries name the dummy
    page, whose positions lie after every query."""
    positions = pages * page_len
    if positions < 1024 or 1024 % page_len:
        return pages
    return -(-positions // 1024) * 1024 // page_len


def _attend_latent_gather(q, slab, tables, lengths, value_dim: int,
                          page_len: int):
    """The reference formulation of the latent decode kernel: each row's
    pages gathered, the absorbed query ``q`` (B, H, entry_width, scaled)
    against every live entry, the entry's first ``value_dim`` columns the
    value. Returns the attended latents (B, H, value_dim)."""
    B, W = tables.shape
    e = slab[tables].reshape(B, W * page_len, slab.shape[-1])
    live = jnp.arange(W * page_len)[None, :] < lengths[:, None]
    s = jnp.einsum("bhe,bte->bht", q, e, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, _MASKED), axis=-1)
    return jnp.einsum("bht,btc->bhc", p.astype(q.dtype), e[..., :value_dim])


def _absorbed_query(q_nope, q_pe, scale, wkv_b, la: LatentSpec):
    """The decode query that meets a latent entry itself: per head ``[q_nope
    W_kvb[:, :nope]^T | q_pe | 0]`` to the entry's stored width, times the
    row's softmax scale (folded in here, in float32, so the kernel needs no
    scale), in the compute dtype."""
    cd = q_nope.dtype
    qt = jnp.einsum("bhn,chn->bhc", q_nope,
                    wkv_b[..., :la.nope_dim].astype(cd),
                    preferred_element_type=jnp.float32)
    q = jnp.concatenate(
        [qt, q_pe.astype(jnp.float32),
         jnp.zeros((*qt.shape[:2], la.entry_width - la.entry_dim))], axis=-1)
    return (q * scale[:, None, None]).astype(cd)


def _attend_sparse_chunk(q, k, v, cc, q_pos, valid, sp: SparseSpec):
    """Prefill's attention of a sparse layer: ``q`` (T, kvh, g, dh) at
    ``q_pos`` (``valid``: the rows that hold a token) over the row's context
    ``k``, ``v`` (L, kvh * dh) (key ``j`` at position ``j``, a token's heads
    side by side) by its compressed keys ``cc`` (L / stride, kvh * dh).
    Under the ``sparse_select`` scope each query's blocks, one KV head at a
    time (:func:`~marlin_tpu.ops.sparse_attention.select_blocks`; every
    block for a query below ``dense_len``, and a chunk that lies wholly
    below it skips the scores), and from them each TILE of queries' list of
    the blocks any of its tokens took
    (:func:`~marlin_tpu.ops.sparse_attention.tile_lists`); under
    ``sparse_attend`` the kernel that walks those lists and meets no other
    block (:func:`~marlin_tpu.ops.paged_attention.sparse_prefill_attention`:
    one form in both regimes; a tile of padding is not computed). Returns
    the attended values (T, kvh, g, dh) and ``(blocks the tiles met, blocks
    their tokens took)``, summed over tiles and KV heads."""
    from ..ops import sparse_attention
    from ..ops.paged_attention import sparse_prefill_attention

    T, kvh, g, dh = q.shape
    nb = k.shape[0] // sp.block

    def one(args):
        qh, ch = args
        idx, taken = sparse_attention.select_blocks(qh, ch, q_pos, sp)
        return sparse_attention.block_mask(idx, taken, nb)

    with jax.named_scope("sparse_select"):
        mask = jax.lax.cond(
            q_pos[-1] < sp.dense_len,
            lambda: jnp.ones((kvh, T, nb), bool),
            lambda: jax.lax.map(one, (
                q.transpose(1, 0, 2, 3),
                cc.reshape(-1, kvh, dh).transpose(1, 0, 2))))
        lists, rounds, words, met, taken = sparse_attention.tile_lists(
            mask, q_pos, valid, sp.block, sparse_attention.tile_tokens(T, g))
    with jax.named_scope("sparse_attend"):
        o = sparse_prefill_attention(q, k, v, q_pos, lists, rounds, words,
                                     sp.block)
    return o, jnp.stack([met, taken])


def _complete_entries(pk, pc, tables, pos, page_len: int, stride: int):
    """Decode's write of a sparse layer's compressed keys: a row whose token
    at ``pos`` ENDS a pooling window (``pos % stride == stride - 1``) reads
    the window's ``2 * stride`` keys back out of the K slab ``pk`` (the
    token's own just written; the first half may lie in the page before) and
    writes their mean to its entry, in the page of ``pos``; any other row
    writes to the dummy page 0. One entry a row and step at most, in place."""
    from ..ops.sparse_attention import compress_keys

    width = pk.shape[-1]
    here = pos // stride * stride
    back = jnp.maximum(here - stride, 0)
    for b in range(pos.shape[0]):  # as _scatter_kv_entries: in-place updates
        halves = [jax.lax.dynamic_slice(
            pk, (tables[b, at[b] // page_len], at[b] % page_len, 0),
            (1, stride, width))[0] for at in (back, here)]
        entry = compress_keys(jnp.concatenate(halves), stride)
        pid = jnp.where(pos[b] % stride == stride - 1,
                        tables[b, pos[b] // page_len], 0)
        pc = jax.lax.dynamic_update_slice(
            pc, entry[None].astype(pc.dtype),
            (pid, pos[b] % page_len // stride, 0))
    return pc


def _select_decode_blocks(q, cc, pos, sp: SparseSpec):
    """Each (row, KV head)'s list of blocks for a decode step: ``q`` (B, kvh,
    g, dh), ``cc`` (B, M, kvh, dh) the rows' compressed keys, ``pos`` (B,).
    Returns ``(idx, taken)`` (B, kvh, S)
    (:func:`~marlin_tpu.ops.sparse_attention.select_blocks` a row and
    head)."""
    from ..ops import sparse_attention

    def one(qh, ch, p):                  # (g, dh), (M, dh), ()
        idx, taken = sparse_attention.select_blocks(qh[None], ch, p[None], sp)
        return idx[0], taken[0]

    per_head = jax.vmap(one, in_axes=(0, 1, None))
    return jax.vmap(per_head)(q, cc, pos)


# ------------------------------------------------------------- paged prefill


@functools.partial(jax.jit, static_argnames=("spec", "page_len"),
                   donate_argnums=(1,))
def _lm_prefill_paged_spec_jit(params, pages, gtable, wtable, chunk,
                               chunk_start, length, seed, temperature, top_p,
                               top_k, spec: ModelSpec, page_len: int,
                               state_slot=None):
    from ..ops import delta_rule, lightning, sparse_attention, ssm as ssm_ops
    from ..ops.paged_attention import fetch_pages
    from .transformer import _pick_token_row, _row_key

    C = chunk.shape[0]
    if C % page_len:
        raise ValueError(f"chunk width {C} must be a multiple of page_len "
                         f"{page_len}")
    cp = C // page_len
    s_page = chunk_start // page_len
    q_pos = chunk_start + jnp.arange(C)
    valid = q_pos < length
    ring = wtable.shape[0]
    wp = spec.window // page_len if spec.has_window else 0
    qb = min(C, max(page_len, C // 4))  # a sliding layer's query sub-block
    # the context a chunk reads, fetched up front and pinned (see
    # transformer._lm_prefill_paged_jit: the slab must not be re-laid-out),
    # a page a copy (XLA's gather of a row wider than 1024 lanes passes over
    # the WHOLE slab: PERF.md section 6, PR 45): a full layer's whole table;
    # a sliding layer's ring slots that hold the window/page_len pages before
    # the chunk, in position order
    Lg = gtable.shape[0] * page_len
    w_slots = wtable[jnp.mod(s_page - wp + jnp.arange(wp), max(ring, 1))]
    ltable = jnp.pad(gtable, (0, flash_table_pages(
        gtable.shape[0], page_len) - gtable.shape[0]))
    # every other kind goes by the global table (a sparse layer's K, V and
    # compressed keys alike; a layer without pages has no slab to fetch)
    by_kind = {"sliding": w_slots, "latent": ltable}
    with jax.named_scope("ctx_gather"):
        ctx = jax.lax.optimization_barrier({
            name: tuple(fetch_pages(t, by_kind.get(ly.attn, gtable))
                        .reshape(-1, *t.shape[2:])
                        for t in _kv_slabs(ly, pages[name]))
            for name, ly in ((f"l{i}", ly)
                             for i, ly in enumerate(spec.layers))})
    g_pos = jnp.arange(Lg)
    w_pos = jnp.concatenate([(s_page - wp) * page_len
                             + jnp.arange(wp * page_len), q_pos])
    x = _embed(spec, params, chunk)
    new_kv, new_state, counts = {}, {}, jnp.zeros((3,), jnp.int32)
    walked = []  # a sparse layer's (blocks its tiles met, their tokens took)
    scored = []  # an indexer's (queries that selected, pairs they scored)
    for i, ly in enumerate(spec.layers):
        name = f"l{i}"

        def mix(xbc, dt, sp, name=name):
            # the row's state slot, entered and left: a row's FIRST chunk
            # enters with zeros whatever the slot's last row left in it
            # (this program is behind every earlier call in the stream);
            # positions past the prompt move neither state nor tail
            sm = spec.ssm
            states, tails = pages[name][2:]
            s0, t0 = _enter_state(chunk_start == 0, states[state_slot],
                                  tails[state_slot])
            conv, t1 = ssm_ops.causal_conv(
                xbc, ssm_ops.slot_tails(t0, sm.conv, sm.conv_dim),
                sp["conv_w"], sp["conv_b"],
                jnp.clip(length - chunk_start, 0, C))
            xs, Bm, Cm = _scan_operands(sm, conv, xbc.dtype)
            with jax.named_scope("ssm_scan"):
                y, s1 = ssm_ops.ssd_chunk_scan(
                    xs, jnp.where(valid[:, None], dt, 0.0),
                    -jnp.exp(sp["A_log"]), Bm, Cm, sp["D"], s0,
                    block=min(sm.chunk, C))
            new_state[name] = (
                jax.lax.dynamic_update_index_in_dim(states, s1, state_slot,
                                                    0),
                jax.lax.dynamic_update_index_in_dim(
                    tails, ssm_ops.tails_slots(t1, sm.conv, sm.conv_dim),
                    state_slot, 0))
            return y.reshape(C, sm.d_inner)

        def mix_delta(qkv, g, beta, lp, name=name):
            # as above, for a linear or a kda layer's delta rule: the slot's
            # state (key_dim, heads * value_dim) entered and left, the
            # padding neither decaying nor stepping (g 0 and b 0 there; g
            # is one a head or one a channel)
            ds = spec.delta or spec.kda
            states, tails = pages[name]
            s0, t0 = _enter_state(chunk_start == 0, states[state_slot],
                                  tails[state_slot])
            tail0 = ssm_ops.slot_tails(t0, ds.conv, ds.conv_dim)
            bias = jnp.zeros((ds.conv_dim,), qkv.dtype)
            # (traced in the order it had: a linear layer's program lowers
            # to the text it lowered to before the scan took the count)
            tokens = jnp.clip(length - chunk_start, 0, C)
            conv, t1 = ssm_ops.causal_conv(qkv, tail0, lp["conv_w"], bias,
                                           tokens)
            q, k, v = _delta_operands(ds, conv, qkv.dtype)
            with jax.named_scope(
                    "delta_scan" if spec.kda is None else "kda_scan"):
                o, s1 = delta_rule.delta_chunk_scan(
                    q, k, v,
                    jnp.where(valid.reshape(C, *(1,) * (g.ndim - 1)), g, 0.0),
                    jnp.where(valid[:, None], beta, 0.0),
                    s0.reshape(ds.key_dim, ds.heads, ds.value_dim),
                    block=min(ds.chunk, C), valid=tokens)
            new_state[name] = (
                jax.lax.dynamic_update_index_in_dim(
                    states, s1.reshape(states.shape[1:]), state_slot, 0),
                jax.lax.dynamic_update_index_in_dim(
                    tails, ssm_ops.tails_slots(t1, ds.conv, ds.conv_dim),
                    state_slot, 0))
            return o

        def mix_conv(s, lp, name=name):
            # a conv layer's slot is its tail alone: entered (zeros at a
            # row's first chunk, whatever the slot held) and left behind
            # the chunk's last VALID input; padding moves nothing
            (tails,) = pages[name]
            (t0,) = _enter_state(chunk_start == 0, tails[state_slot])
            conv, t1 = ssm_ops.causal_conv(
                s, t0, lp["conv_w"], jnp.zeros((s.shape[1],), s.dtype),
                jnp.clip(length - chunk_start, 0, C))
            new_state[name] = (
                jax.lax.dynamic_update_index_in_dim(tails, t1, state_slot, 0),)
            return conv

        def mix_lightning(q, k, v, lp, name=name):
            # a lightning layer's slot is its state alone: entered (zeros at
            # a row's first chunk) and left behind the chunk's last VALID
            # token; padding neither decays it nor adds to it
            (states,) = pages[name]
            (s0,) = _enter_state(chunk_start == 0, states[state_slot])
            with jax.named_scope("lightning_scan"):
                o, s1 = lightning.lightning_chunk_scan(
                    q, k, v, jnp.log(lp["decay"]), valid, s0,
                    block=min(spec.lightning.chunk, C))
            new_state[name] = (
                jax.lax.dynamic_update_index_in_dim(states, s1, state_slot,
                                                    0),)
            return o

        def attend_sparse(q, k, v, name=name):
            # the chunk's keys and values join the context as a full
            # layer's do; its compressed entries (those whose windows END
            # in the chunk: the first reaches `stride` tokens back into the
            # page before, zeros before position 0, where no window is
            # complete) are written with its pages and join the row's
            sp = spec.sparse
            ck, cv, cc = ctx[name]
            k2, v2 = (new.astype(c.dtype).reshape(C, -1)
                      for new, c in ((k, ck), (v, cv)))
            before = jnp.where(chunk_start == 0, 0, jax.lax.dynamic_slice(
                ck, (jnp.maximum(chunk_start - sp.stride, 0), 0),
                (sp.stride, ck.shape[1])))
            fresh = sparse_attention.compress_keys(
                jnp.concatenate([before, k2]), sp.stride)
            new_kv[name] = (k, v, fresh)
            ck = jax.lax.dynamic_update_slice(ck, k2, (chunk_start, 0))
            cv = jax.lax.dynamic_update_slice(cv, v2, (chunk_start, 0))
            cc = jax.lax.dynamic_update_slice(
                cc, fresh, (chunk_start // sp.stride, 0))

            o, met = _attend_sparse_chunk(q, ck, cv, cc, q_pos, valid, sp)
            walked.append(met)
            return o

        def attend_latent(q_nope, q_pe, entry, scale, wkv_b, index=None,
                          name=name):
            la = spec.latent
            new_kv[name] = (entry,) if index is None else (entry, index[1])
            ce, *ck = ctx[name]
            ce = jax.lax.dynamic_update_slice(ce, entry.astype(ce.dtype),
                                              (chunk_start, 0))

            def every(ce):
                if C % 128 == 0:   # the flash kernel's rows: lane tiles
                    return _attend_latent_flash(q_nope, q_pe, ce, wkv_b,
                                                scale, chunk_start, la)
                return _attend_latent_blocks(q_nope, q_pe, ce, wkv_b, scale,
                                             q_pos, la, block=C)

            if index is None:
                return every(ce)
            # a layer with an indexer: a chunk wholly below `topk` attends
            # every position it can see (all of them lie in the context's
            # first `topk` entries); any other scores the row's index keys,
            # takes each query's `topk` tokens and gathers THOSE entries,
            # absorbed as decode is: the whole context is neither
            # up-projected nor met
            from ..ops import dsa

            qi, ki, w = index
            ck = jax.lax.dynamic_update_slice(
                ck[0], ki.astype(ck[0].dtype), (chunk_start, 0))
            topk = la.indexer.topk

            def selected():
                with jax.named_scope("dsa_index"):
                    if C % 128 == 0 and ck.shape[0] % 512 == 0:
                        scores = dsa.index_scores_chunk(
                            qi, w, ck, chunk_start,
                            jnp.clip(length - chunk_start, 0, C))
                    else:
                        scores = dsa.index_scores(qi, w, ck, q_pos)
                ot, _ = _attend_selected_tokens(
                    _absorbed_query(q_nope, q_pe, scale, wkv_b, la), ce,
                    scores, q_pos + 1, la, _DSA_TILE, live=valid)
                return jnp.einsum(
                    "thc,chv->thv", ot,
                    wkv_b[..., la.nope_dim:].astype(ot.dtype),
                    preferred_element_type=jnp.float32).astype(ce.dtype)

            scored.append(jnp.where(
                chunk_start + C <= topk, 0,
                jnp.stack([jnp.sum(valid), jnp.sum(jnp.where(
                    valid, q_pos + 1, 0))])).astype(jnp.int32))
            return jax.lax.cond(
                chunk_start + C <= topk,
                lambda: every(ce[:max(topk, C)]).astype(ce.dtype), selected)

        def attend(q, k, v, name=name, ly=ly):
            new_kv[name] = (k, v)
            # the context as the slab holds it, a token's heads in one row;
            # the chunk's own entries join it in that form
            ck, cv = ctx[name]
            k, v = (new.astype(c.dtype).reshape(C, -1)
                    for new, c in ((k, ck), (v, cv)))

            def heads(c):
                return c.reshape(c.shape[0], spec.kv_heads, spec.head_dim)

            if ly.attn == "full":
                ck = jax.lax.dynamic_update_slice(ck, k, (chunk_start, 0))
                cv = jax.lax.dynamic_update_slice(cv, v, (chunk_start, 0))
                return _attend_dense(q, heads(ck), heads(cv), q_pos, g_pos,
                                     jnp.ones((Lg,), bool), None, block=C)
            ck = heads(jnp.concatenate([ck, k]))
            cv = heads(jnp.concatenate([cv, v]))
            # a band, not a square: the queries a sub-block at a time, each
            # against the window before it and itself (the context holds
            # exactly `window` positions before the chunk, so the slices
            # are static)
            outs = []
            for o in range(0, C, qb):
                keys = slice(o, o + spec.window + qb)
                outs.append(_attend_dense(
                    q[o:o + qb], ck[keys], cv[keys], q_pos[o:o + qb],
                    w_pos[keys], w_pos[keys] >= 0, spec.window))
            return jnp.concatenate(outs)

        x, c = layer_forward(spec, i, params[name], x, q_pos, valid,
                             {"latent": attend_latent,
                              "sparse": attend_sparse}.get(ly.attn, attend),
                             {"linear": mix_delta, "kda": mix_delta,
                              "conv": mix_conv,
                              "lightning": mix_lightning}.get(ly.attn, mix))
        counts = counts + c
    # write the chunk's pages, one dynamic update a page (transformer.py has
    # the reason). A page wholly past the prompt goes to the dummy: in a
    # ring its slot may still hold a page the window needs
    new_pages = {}
    for i, ly in enumerate(spec.layers):
        name = f"l{i}"
        slabs = _kv_slabs(ly, pages[name])
        fresh = [new.astype(t.dtype).reshape(cp, *t.shape[1:])
                 for new, t in zip(new_kv.get(name, ()), slabs)]
        for j in range(cp):
            pid = (wtable[jnp.mod(s_page + j, ring)] if ly.attn == "sliding"
                   else gtable[s_page + j])
            pid = jnp.where(chunk_start + j * page_len < length, pid, 0)
            slabs = tuple(
                jax.lax.dynamic_update_index_in_dim(t, pg[j], pid, 0)
                for t, pg in zip(slabs, fresh))
        new_pages[name] = slabs + new_state.get(name, ())
    idx = jnp.clip(length - 1 - chunk_start, 0, C - 1)
    logits = _head_logits(spec, params, x[idx])
    first = _pick_token_row(temperature, top_p, top_k, logits,
                            _row_key(seed, 0))
    if walked:  # after the expert layers' three
        counts = jnp.concatenate([counts, sum(walked).astype(jnp.int32)])
    if scored:  # likewise: queries of ONE layer (all alike), pairs of all
        counts = jnp.concatenate([counts, jnp.stack([
            scored[0][0], sum(sc[1] for sc in scored)])])
    return new_pages, first, counts, logits


def _prefill_args(params, pages, tables, chunk, chunk_start, length,
                  spec: ModelSpec, page_len: int, seed=0, temperature=0.0,
                  top_p=None, top_k=None):
    gtable, wtable, *state_slot = tables
    return (params, pages, jnp.asarray(gtable, jnp.int32),
            jnp.asarray(wtable, jnp.int32), jnp.asarray(chunk, jnp.int32),
            jnp.asarray(chunk_start, jnp.int32),
            jnp.asarray(length, jnp.int32), jnp.asarray(seed, jnp.uint32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(1.0 if top_p is None else top_p, jnp.float32),
            jnp.asarray(0 if top_k is None else top_k, jnp.int32)), {
                "spec": spec, "page_len": page_len,
                **_state_slots(spec, "state_slot", state_slot)}


def _enter_state(fresh, *arrays):
    """What a prefill chunk enters with: the slot's arrays (state and tail,
    or a tail alone), or zeros where the chunk starts at position 0
    (``fresh``): a slot is not wiped when it changes hands. A row whose
    FIRST chunk starts past 0 (a prefix hit) enters with what the engine
    copied into its slot ahead of that chunk: the snapshot of the state at
    the prefix's end (:func:`state_slot_copy`)."""
    return tuple(jnp.where(fresh, 0, a) for a in arrays)


def _kv_slabs(ly: LayerSpec, arrays: tuple) -> tuple:
    """A layer's arrays that a page id indexes (what follows them a state
    slot does: :func:`init_kv_pages`)."""
    if not ly.owns_pages:
        return ()
    return arrays[:2] if ly.ssm else arrays


def _state_slots(spec: ModelSpec, name: str, given) -> dict:
    """The programs' state-slot argument, from the tail of ``tables``: there
    for a spec with state, and only for one."""
    if bool(given) != spec.has_state:
        raise ValueError(
            "a model with recurrent mixers takes (global tables, window "
            "rings, state slots); any other (global tables, window rings)")
    return {name: jnp.asarray(given[0], jnp.int32)} if given else {}


def prefill_paged(params, pages, tables, chunk, chunk_start, length,
                  spec: ModelSpec, page_len: int, seed=0, temperature=0.0,
                  top_p=None, top_k=None):
    """:func:`~marlin_tpu.models.transformer.lm_prefill_paged` for a spec:
    ``tables`` is the row's ``(global table, window ring)`` and, for a model
    with state-space mixers, its state slot after them. Returns
    ``(pages, first, counts, logits)``: ``counts`` the expert layers'
    ``(assignments, local assignments, experts touched)`` summed over
    layers and, for a model with sparse layers, after them ``(blocks the
    chunk's tiles met, blocks their tokens took)`` summed over those layers
    (:func:`_attend_sparse_chunk`), ``logits`` the float32 logits ``first``
    was picked from."""
    args, static = _prefill_args(params, pages, tables, chunk, chunk_start,
                                 length, spec, page_len, seed, temperature,
                                 top_p, top_k)
    return _lm_prefill_paged_spec_jit(*args, **static)


# -------------------------------------------------------------- paged decode


def _attend_gather(q, pk, pv, tables, lengths, first_page, lower,
                   page_len: int):
    """The reference formulation of the decode kernel: each row's pages
    gathered in position order (ring slot ``(first_page + w) % W``), dense
    masked softmax over them. ``q`` (B, kvh, g, dh); the slabs as
    :func:`init_kv_pages` lays them out."""
    B, W = tables.shape
    slots = jnp.mod(first_page[:, None] + jnp.arange(W)[None, :], W)
    pids = jnp.take_along_axis(tables, slots, axis=1)
    heads = (B, W * page_len, q.shape[1], q.shape[3])
    k = pk[pids].reshape(heads)
    v = pv[pids].reshape(heads)
    pos = first_page[:, None] * page_len + jnp.arange(W * page_len)[None, :]
    live = (pos >= lower[:, None]) & (pos < lengths[:, None])
    s = jnp.einsum("bkgd,btkd->bkgt", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(live[:, None, None, :], s, _MASKED), axis=-1)
    return jnp.einsum("bkgt,btkd->bkgd", p.astype(q.dtype), v)


@functools.partial(jax.jit, static_argnames=("spec", "page_len", "kernel"),
                   donate_argnums=(1,))
def _lm_decode_paged_spec_jit(params, pages, gtables, wtables, positions,
                              cur_tokens, steps_done, seeds, temperature,
                              top_p, top_k, spec: ModelSpec, page_len: int,
                              kernel: str, prev_tokens=None, prev_index=None,
                              state_slots=None):
    from ..ops import delta_rule, lightning, sparse_attention, ssm as ssm_ops
    from ..ops.paged_attention import (fetch_pages, paged_decode_attention,
                                       paged_decode_attention_blocks,
                                       paged_decode_attention_latent)
    from .transformer import (_pick_token_rows, _scatter_kv_entries,
                              _select_tokens)

    cur_tokens = _select_tokens(cur_tokens, prev_tokens, prev_index)
    B, Wg = gtables.shape
    ring = wtables.shape[1]
    rows = jnp.arange(B)
    pos = jnp.minimum(positions, Wg * page_len - 1)
    live = gtables[:, 0] != 0     # a dummy row's table starts at the dummy
    lengths = pos + 1             # the entry written below is live
    page = pos // page_len
    off = pos % page_len
    zero = jnp.zeros((B,), jnp.int32)
    lower = jnp.maximum(pos - spec.window + 1, 0)
    # per class: (tables, the page id this step writes, first page the
    # kernel visits, lowest visible position). Every kind but a sliding layer
    # goes by the global table (a latent and a sparse layer too: every
    # position; a layer without pages reads none of it)
    one_table = (gtables, gtables[rows, page], zero, zero)
    per_class = {}
    if spec.has_window:
        per_class["sliding"] = (wtables, wtables[rows, jnp.mod(page, ring)],
                                lower // page_len, lower)
    x = _embed(spec, params, cur_tokens)
    new_pages, counts = {}, jnp.zeros((3,), jnp.int32)
    read = []  # a layer with an indexer: (entries attended, held, rows past)
    for i, ly in enumerate(spec.layers):
        name = f"l{i}"
        tables, pids, first_page, low = per_class.get(ly.attn, one_table)

        def mix(xbc, dt, sp, name=name):
            # one token a row: each live row's slot read and written once,
            # tail and state alike; the rows no live row fills name the
            # dummy slot 0
            sm = spec.ssm
            states, tails = pages[name][2:]
            with jax.named_scope("ssm_update"):
                conv, tails = ssm_ops.conv_step_slots(
                    tails, state_slots, xbc, sp["conv_w"], sp["conv_b"],
                    kernel=kernel)
                xs, Bm, Cm = _scan_operands(sm, conv, xbc.dtype)
                states, y = ssm_ops.ssd_decode_update(
                    states, state_slots, xs, dt, -jnp.exp(sp["A_log"]), Bm,
                    Cm, sp["D"], kernel=kernel)
            new_pages[name] += (states, tails)
            return y.reshape(B, sm.d_inner)

        def mix_delta(qkv, g, beta, lp, name=name):
            # as above, for a linear or a kda layer: its two arrays are all
            # it has
            ds = spec.delta or spec.kda
            states, tails = pages[name]
            with jax.named_scope(
                    "delta_update" if spec.kda is None else "kda_update"):
                conv, tails = ssm_ops.conv_step_slots(
                    tails, state_slots, qkv, lp["conv_w"],
                    jnp.zeros((ds.conv_dim,), qkv.dtype), kernel=kernel)
                q, k, v = _delta_operands(ds, conv, qkv.dtype)
                states, o = delta_rule.delta_decode_update(
                    states, state_slots, q, k, v, g, beta, kernel=kernel)
            new_pages[name] = (states, tails)
            return o

        def mix_conv(s, lp, name=name):
            # a conv layer: each live row's tail read and written in place
            (tails,) = pages[name]
            conv, t1 = ssm_ops.conv_step(
                s, tails[state_slots], lp["conv_w"],
                jnp.zeros((s.shape[1],), s.dtype))
            new_pages[name] = (tails.at[state_slots].set(t1),)
            return conv

        def attend(q, k, v, name=name, ly=ly, tables=tables, pids=pids,
                   first_page=first_page, low=low):
            pk, pv = _kv_slabs(ly, pages[name])
            pk, pv = _scatter_kv_entries(pk, pv, k.astype(pk.dtype),
                                         v.astype(pv.dtype), pids, off)
            new_pages[name] = (pk, pv)
            if kernel != "pallas":
                return _attend_gather(q, pk, pv, tables, lengths, first_page,
                                      low, page_len)
            if ly.attn == "full":
                return paged_decode_attention(q, pk, pv, tables, lengths)
            return paged_decode_attention(q, pk, pv, tables, lengths,
                                          first_page=first_page, lower=low)

        def mix_lightning(q, k, v, lp, name=name):
            # a lightning layer: each live row's state read and written once
            (states,) = pages[name]
            with jax.named_scope("lightning_update"):
                states, o = lightning.lightning_decode_update(
                    states, state_slots, q, k, v, jnp.log(lp["decay"]),
                    kernel=kernel)
            new_pages[name] = (states,)
            return o

        def attend_sparse(q, k, v, name=name, pids=pids):
            # the token's key and value as a full layer's; the compressed
            # entry of a window this token ends; then each (row, KV head)
            # picks its blocks by the row's compressed keys and attends them
            sp = spec.sparse
            pk, pv, pc = pages[name]
            pk, pv = _scatter_kv_entries(pk, pv, k.astype(pk.dtype),
                                         v.astype(pv.dtype), pids, off)
            pc = _complete_entries(pk, pc, gtables, pos, page_len, sp.stride)
            new_pages[name] = (pk, pv, pc)
            with jax.named_scope("sparse_select"):
                cc = (fetch_pages(pc, gtables.reshape(-1))
                      if kernel == "pallas" else pc[gtables])
                idx, taken = _select_decode_blocks(
                    q, cc.reshape(B, -1, spec.kv_heads, spec.head_dim), pos,
                    sp)
            if kernel != "pallas":
                return sparse_attention.attend_blocks_gather(
                    q, pk, pv, gtables, idx, taken, lengths, sp.block)
            return paged_decode_attention_blocks(
                q, pk, pv, gtables, idx, taken.sum(axis=-1), lengths,
                sp.block)

        def attend_latent(q_nope, q_pe, entry, scale, wkv_b, index=None,
                          name=name, tables=tables, pids=pids):
            # the entry and, of a layer with an indexer, the index key
            slabs = pages[name]
            news = (entry,) if index is None else (entry, index[1])
            for b in range(B):  # as _scatter_kv_entries: in-place updates
                slabs = tuple(jax.lax.dynamic_update_slice(
                    t, new[b].astype(t.dtype)[None, None],
                    (pids[b], off[b], 0)) for t, new in zip(slabs, news))
            slab = slabs[0]
            new_pages[name] = slabs
            la = spec.latent
            q = _absorbed_query(q_nope, q_pe, scale, wkv_b, la)
            if index is not None:
                # each row scores its own index keys in place, takes its
                # `topk` tokens and reads THOSE entries out of the slab
                from ..ops import dsa

                qi, _, w = index
                with jax.named_scope("dsa_index"):
                    scores = (dsa.index_scores_paged if kernel == "pallas"
                              else dsa.index_scores_gather)(
                                  qi, w, slabs[1], tables, lengths)

                def rows_of(idx, at):   # positions -> rows of the flat slab
                    return (tables[at[:, None], idx // page_len] * page_len
                            + idx % page_len)

                ot, took = _attend_selected_tokens(
                    q, slab.reshape(-1, slab.shape[-1]), scores, lengths, la,
                    B, rows_of)
                read.append(jnp.stack([
                    jnp.sum(jnp.where(live, took, 0)),
                    jnp.sum(jnp.where(live, lengths, 0)),
                    jnp.sum(live & (lengths > la.indexer.topk))]))
            elif kernel == "pallas":
                ot = paged_decode_attention_latent(q, slab, tables, lengths,
                                                   value_dim=la.kv_rank)
            else:
                ot = _attend_latent_gather(q, slab, tables, lengths,
                                           la.kv_rank, page_len)
            return jnp.einsum("bhc,chv->bhv", ot,
                              wkv_b[..., la.nope_dim:].astype(ot.dtype),
                              preferred_element_type=jnp.float32)

        x, c = layer_forward(spec, i, params[name], x, pos, live,
                             {"latent": attend_latent,
                              "sparse": attend_sparse}.get(ly.attn, attend),
                             {"linear": mix_delta, "kda": mix_delta,
                              "conv": mix_conv,
                              "lightning": mix_lightning}.get(ly.attn, mix))
        counts = counts + c
    if read:  # entries summed over the layers, the rows those of ONE layer
        counts = jnp.concatenate([counts, jnp.stack([
            sum(r[0] for r in read), sum(r[1] for r in read),
            read[0][2]]).astype(jnp.int32)])
    logits = _head_logits(spec, params, x)
    nxt = _pick_token_rows(temperature, top_p, top_k, logits, seeds,
                           steps_done)
    return new_pages, nxt, counts, logits


def _decode_args(params, pages, tables, positions, cur_tokens, steps_done,
                 seeds, temperature, top_p, top_k, spec: ModelSpec,
                 page_len: int, kernel: str, prev_tokens=None,
                 prev_index=None):
    from .transformer import _fed_tokens

    gtables, wtables, *state_slots = tables
    as_i32 = lambda v: jnp.asarray(v, jnp.int32)  # noqa: E731
    return (params, pages, as_i32(gtables), as_i32(wtables),
            as_i32(positions), as_i32(cur_tokens), as_i32(steps_done),
            jnp.asarray(seeds, jnp.uint32),
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(top_p, jnp.float32), as_i32(top_k)), {
                "spec": spec, "page_len": page_len, "kernel": kernel,
                **_fed_tokens(prev_tokens, prev_index),
                **_state_slots(spec, "state_slots", state_slots)}


def decode_paged(params, pages, tables, positions, cur_tokens, steps_done,
                 seeds, temperature, top_p, top_k, spec: ModelSpec,
                 page_len: int, kernel: str, prev_tokens=None,
                 prev_index=None):
    """:func:`~marlin_tpu.models.transformer.lm_decode_paged` for a spec:
    ``tables`` is ``(global tables (B, W), window rings (B, ring))`` and,
    for a model with state-space mixers, ``state slots (B,)`` after them; a
    row whose global table starts at the dummy page is a dummy row, is
    routed to no expert and names the dummy state slot 0. Returns ``(pages, next_tokens, counts, logits)`` as
    :func:`prefill_paged`; of a model with an indexer ``counts`` holds,
    after the expert layers' three, what the live rows' layers read: the
    entries their lists attended and the tokens their contexts hold (both
    summed over rows and layers) and the rows past ``index_topk``."""
    args, static = _decode_args(params, pages, tables, positions, cur_tokens,
                                steps_done, seeds, temperature, top_p, top_k,
                                spec, page_len, kernel, prev_tokens,
                                prev_index)
    return _lm_decode_paged_spec_jit(*args, **static)


def precompile_paged(prefills, decodes) -> None:
    """Compile side by side the programs that the given
    :func:`prefill_paged` / :func:`decode_paged` calls (one argument tuple
    each) will run (:func:`_compile_side_by_side`): one program of nine
    unrolled layers with their kernels takes the compiler half a minute, and
    a bucketed engine has six."""
    _compile_side_by_side(
        [(_lm_prefill_paged_spec_jit, *_prefill_args(*a)) for a in prefills]
        + [(_lm_decode_paged_spec_jit, *_decode_args(*a)) for a in decodes])


def _copy_entry(spec: ModelSpec, pages, src, dst, state: bool):
    """Entry ``src`` onto entry ``dst`` of every layer's state arrays
    (``state``) or page arrays (not), the others handed through."""
    out = {}
    for i, ly in enumerate(spec.layers):
        arrays = pages[f"l{i}"]
        n = len(_kv_slabs(ly, arrays))
        kv, st = arrays[:n], arrays[n:]
        moved = tuple(t.at[dst].set(t[src]) for t in (st if state else kv))
        out[f"l{i}"] = kv + moved if state else moved + st
    return out


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def _state_slot_copy_jit(pages, src, dst, spec: ModelSpec):
    with jax.named_scope("state_snapshot"):
        return _copy_entry(spec, pages, src, dst, state=True)


def state_slot_copy(pages, src, dst, spec: ModelSpec):
    """Copy state slot ``src`` onto slot ``dst`` in every layer that keeps
    something there, each array the layer has (state and convolution tail,
    or a short convolution's tail alone) (``pages`` DONATED; ``src``
    / ``dst`` traced: ONE compiled program an engine). Taking a snapshot and
    entering from one are both this program, dispatched in the stream
    behind the chunk that wrote ``src`` and ahead of whatever changes it
    next; its operations sit under the ``state_snapshot`` scope."""
    return _state_slot_copy_jit(pages, jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32), spec=spec)


@functools.partial(jax.jit, static_argnames=("spec",), donate_argnums=(0,))
def _kv_page_copy_spec_jit(pages, src, dst, spec: ModelSpec):
    return _copy_entry(spec, pages, src, dst, state=False)


def kv_page_copy(pages, src, dst, spec: ModelSpec):
    """:func:`~marlin_tpu.models.transformer.kv_page_copy` for a spec with
    state: page ``src`` onto page ``dst`` in the arrays a page id indexes,
    and in no other (a state slot's arrays go by another id)."""
    return _kv_page_copy_spec_jit(pages, jnp.asarray(src, jnp.int32),
                                  jnp.asarray(dst, jnp.int32), spec=spec)


prefill_paged._cache_size = _lm_prefill_paged_spec_jit._cache_size
decode_paged._cache_size = _lm_decode_paged_spec_jit._cache_size
