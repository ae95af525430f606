"""Pluggable block-selection policies + the ``DecodeOptions`` decode API.

SeerAttention-R's learned gate is one point in a family of block-selection
strategies ("The Sparse Frontier": the interesting questions are
comparative — budget vs. method vs. context length). This module makes the
strategy a first-class, swappable object instead of a hardwired code path:

  GatePolicy            the paper's learned gate (kernels/gate_select.py;
                        bitwise-identical to the pre-policy decode path)
  QuestPolicy           training-free query-aware selection from per-block
                        key min/max metadata (core/quest.py, Tang et al.)
  OraclePolicy          exact top-k over the true attention block scores
                        (core/oracle.py) — the quality ceiling
  DensePolicy           no selection; full dense decode attention
  SlidingWindowPolicy   sink blocks + trailing local window, no extra state

Every policy is a frozen (hashable) dataclass, so it is jit-STATIC: it
rides inside ``DecodeOptions`` which the engines close over per compiled
step. ``DecodeOptions`` replaces the old ``sparse: bool, sparse_impl: str``
kwarg threading through engine -> ModelApi -> model -> ops:

    old                                   new
    ------------------------------------  ---------------------------------
    sparse=True (gate selection)          DecodeOptions()  # GatePolicy
    sparse=False                          DecodeOptions(policy=DensePolicy())
    sparse_impl="pallas"                  DecodeOptions(kernel_impl="pallas")
    sparse_impl="sharded"                 DecodeOptions(kernel_impl="sharded")
    greedy=True                           DecodeOptions(sampling=GREEDY)
    (unavailable)                         sampling=SamplingParams(...)
    (unavailable)                         budget_override=<tokens>
    (unavailable)                         policy=QuestPolicy()/OraclePolicy()/...

A policy consumes ``SelectionInputs`` — the per-step view the attention
layer already has in hand (queries, the Kg cache or its paged twin, the
raw K cache, lengths) — and returns selected LOGICAL block ids
``[B, Hkv, k]`` int32 with -1 padding, the contract of the block-sparse
decode kernels. Policies other than the gate rank with plain top-k
(``sparsity.budget_select``): their scores are bounds/maxima, not
calibrated probabilities, so the threshold method does not apply to them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import (Any, Dict, NamedTuple, Optional, Protocol, Sequence,
                    Tuple, runtime_checkable)

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import kcache as kc
from repro.core import sparsity as sp
from repro.serve.sampling import GREEDY, SamplingParams

KERNEL_IMPLS = ("ref", "pallas", "pallas_interpret", "sharded")

# per-layer staging of a SelectionSchedule (jit-static ints; threaded as a
# scan-xs array through the decode layer loop)
STAGE_DENSE, STAGE_SELECT, STAGE_REUSE = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class SelectionSchedule:
    """STEP-LEVEL selection plan across the layer stack (jit-static).

    Block selection is strongly correlated across layers and heads on
    reasoning traces (TidalDecode; "Less Is More"), so selection need not
    run in every layer: a schedule designates which layers COMPUTE a fresh
    selection and which REUSE the step's current plan (the ``[B, Hkv, k]``
    index list carried through the layer loop).

      dense_first_n      leading layers run DENSE decode attention (their
                         block choices are the least stable; they also
                         seed no plan)
      select_layer       the layer that computes the step's plan. None
                         (default) = every sparse layer selects for itself
                         — today's behavior, bitwise-pinned
      correction_layers  later layers that RE-select, refreshing the plan
                         (TidalDecode's re-selection layer)
      unify_heads        max-reduce selection scores across KV heads so a
                         single block list drives every head ("Less Is
                         More" head-unified selection). Orthogonal to the
                         layer staging; forces the jnp scoring path for
                         the gate (the fused kernel scores per head)

    Layers in ``[dense_first_n, select_layer)`` run dense as well: no plan
    exists yet at that depth (the schedule validates the window but the
    stage derivation makes the rule explicit). The DEFAULT schedule is the
    trivial one — every layer selects, no unification — and takes the
    exact pre-schedule code path (bitwise-identical to
    tests/golden_policy.npz).
    """
    dense_first_n: int = 0
    select_layer: Optional[int] = None
    correction_layers: Tuple[int, ...] = ()
    unify_heads: bool = False

    def __post_init__(self):
        if self.dense_first_n < 0:
            raise ValueError(
                f"dense_first_n must be >= 0: {self.dense_first_n}")
        if self.select_layer is None:
            if self.correction_layers:
                raise ValueError("correction_layers require a select_layer "
                                 "(no plan exists to correct)")
            return
        if self.select_layer < self.dense_first_n:
            raise ValueError(
                f"select_layer {self.select_layer} lies inside the dense "
                f"prefix (dense_first_n={self.dense_first_n})")
        cl = tuple(self.correction_layers)
        if list(cl) != sorted(set(cl)):
            raise ValueError(
                f"correction_layers must be sorted and unique: {cl}")
        if cl and cl[0] <= self.select_layer:
            raise ValueError(
                f"correction_layers must come after select_layer "
                f"{self.select_layer}: {cl}")

    @property
    def is_trivial(self) -> bool:
        """True for the default schedule: every layer selects for itself,
        per-head — the pre-schedule decode path, bitwise-pinned."""
        return (self.dense_first_n == 0 and self.select_layer is None
                and not self.unify_heads)

    @property
    def needs_plan(self) -> bool:
        """True when a selection plan must be CARRIED through the layer
        loop (some layer runs dense or reuses). ``unify_heads`` alone does
        not need a plan — every layer still selects for itself."""
        return self.dense_first_n > 0 or self.select_layer is not None

    def layer_stages(self, n_layers: int) -> Tuple[int, ...]:
        """Per-layer stage (STAGE_DENSE/SELECT/REUSE) for an
        ``n_layers``-deep stack — the jit-static staging array."""
        if self.dense_first_n >= n_layers and self.select_layer is None \
                and self.dense_first_n > 0:
            raise ValueError(
                f"dense_first_n={self.dense_first_n} covers the whole "
                f"{n_layers}-layer stack; use DensePolicy instead")
        if self.select_layer is not None and self.select_layer >= n_layers:
            raise ValueError(
                f"select_layer {self.select_layer} out of range for "
                f"{n_layers} layers")
        if self.correction_layers and \
                self.correction_layers[-1] >= n_layers:
            raise ValueError(
                f"correction_layers {self.correction_layers} out of range "
                f"for {n_layers} layers")
        stages = []
        for layer in range(n_layers):
            if layer < self.dense_first_n:
                stages.append(STAGE_DENSE)
            elif self.select_layer is None:
                stages.append(STAGE_SELECT)
            elif layer == self.select_layer \
                    or layer in self.correction_layers:
                stages.append(STAGE_SELECT)
            elif layer < self.select_layer:
                stages.append(STAGE_DENSE)     # no plan exists yet
            else:
                stages.append(STAGE_REUSE)
        return tuple(stages)


def platform_kernel_impl() -> str:
    """The decode kernels of the default backend: the compiled Pallas
    kernels on a TPU, the jnp kernels (``kernels/ref.py``) anywhere else.
    One decode path per platform — a TPU never runs the jnp reference
    unless the caller names ``kernel_impl="ref"``."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def select_impl(kernel_impl: str) -> str:
    """Map the attention-kernel impl to the gate-select impl: the Pallas
    paths run selection in-kernel too; everything else (ref, sharded) uses
    the jnp twin."""
    return kernel_impl if kernel_impl in ("pallas", "pallas_interpret") \
        else "ref"


class SelectionInputs(NamedTuple):
    """Everything a selection policy may consume for ONE decode step.

    Built by the model's attention layer; contiguous and paged decode fill
    different cache views (the unused ones stay None). All cache views are
    HEAD-MAJOR (the decode-path layout invariant).
    """
    q_nope: jnp.ndarray                 # [B, 1, H, Dh] pre-rope queries
    qr: jnp.ndarray                     # [B, 1, H, Dh] post-rope queries
    pos: jnp.ndarray                    # [B, 1] query positions
    new_len: jnp.ndarray                # [B] kv length incl. the new token
    gate_params: Optional[Dict[str, Any]] = None   # per-layer gate or None
    # contiguous views
    kg: Optional[jnp.ndarray] = None           # [B, Hkv, nb, Dg]
    k_cache: Optional[jnp.ndarray] = None      # [B, Hkv, S, Dh] post-rope
    # paged views: the layer-stacked pools, read at ``layer``
    kg_pages: Optional[jnp.ndarray] = None     # [L, P, Hkv, Dg]
    k_pages: Optional[jnp.ndarray] = None      # [L, P, Hkv, ps, Dh] post-rope
    page_table: Optional[jnp.ndarray] = None   # [B, npt] int32
    layer: Optional[jnp.ndarray] = None        # [] int32 layer index
    # selection-metadata cache views (core.metacache; policies with
    # ``needs_meta``): contiguous incremental min/max, or the paged pools
    meta_kmin: Optional[jnp.ndarray] = None    # [B, Hkv, nb, Dh] float32
    meta_kmax: Optional[jnp.ndarray] = None    # [B, Hkv, nb, Dh] float32
    kmin_pages: Optional[jnp.ndarray] = None   # [L, P, Hkv, Dh] float32
    kmax_pages: Optional[jnp.ndarray] = None   # [L, P, Hkv, Dh] float32
    # int8 K pool dequant scales (ISSUE 9): policies that read raw
    # ``k_pages`` (trailing-block recompute, reference gathers) must
    # dequantize first — selection consumes what attention will read
    k_scale_pages: Optional[jnp.ndarray] = None  # [L, P, Hkv, 1] float32

    @property
    def n_kv_heads(self) -> int:
        """Hkv from whichever cache view is present (all are head-major:
        heads on axis 1 of the contiguous views, axis 2 of the stacked
        pools) — the single derivation every policy uses."""
        for view, axis in ((self.kg, 1), (self.k_cache, 1),
                           (self.kg_pages, 2), (self.k_pages, 2)):
            if view is not None:
                return view.shape[axis]
        raise ValueError("SelectionInputs carries no cache view")

    def n_blocks(self, block_size: int) -> int:
        """Static logical-block count of this step's view."""
        if self.kg is not None:
            return self.kg.shape[2]
        if self.page_table is not None:
            return self.page_table.shape[1]
        return self.k_cache.shape[2] // block_size


@runtime_checkable
class SelectionPolicy(Protocol):
    """Hashable, jit-static block-selection strategy.

    ``dense``: the attention layer skips selection and runs dense decode.
    ``needs_gate``: requires trained gate params (layers without a gate
    fall back to dense, preserving the old ``sparse=True`` semantics).
    ``needs_meta``: reads the incremental selection-metadata cache
    (core.metacache) — the model threads/advances it only for these
    policies, the same advance-only-for-the-reader rule as the Kg cache.
    ``reads_full_kv``: selection itself reads the whole K cache (dense
    attention, or a cache-sized reference gather) — such policies cannot
    run with RaaS page eviction (ISSUE 7), which assumes only SELECTED
    blocks' K/V are ever read so evicted pages are detectable by the
    touched-pages telemetry.
    """
    dense: bool
    needs_gate: bool
    needs_meta: bool
    reads_full_kv: bool

    def select(self, inp: SelectionInputs, cfg: ModelConfig, *,
               impl: str = "ref",
               max_selected: Optional[int] = None,
               unify_heads: bool = False) -> jnp.ndarray:
        """-> selected logical block ids [B, Hkv, k] int32, -1 padding.

        ``unify_heads`` (SelectionSchedule): max-reduce the policy's
        selection scores across KV heads before ranking, so the returned
        rows are IDENTICAL for every head (one plan drives all heads)."""
        ...


def _gathered_k(inp: SelectionInputs) -> jnp.ndarray:
    """Per-row head-major K view for the REFERENCE metadata policies
    (QuestRecompute/Oracle): the contiguous cache as-is, or the paged
    gather. The paged gather is a cache-sized copy — acceptable for these
    reference/ceiling policies only; neither the gate nor the cached
    QuestPolicy hot path ever takes it."""
    if inp.k_cache is not None:
        return inp.k_cache
    from repro.serve import paging as pg
    return pg.gather_kv(inp.k_pages, inp.layer, inp.page_table,
                        inp.k_scale_pages)


def _grouped_q(inp: SelectionInputs) -> jnp.ndarray:
    """Post-rope query regrouped [B, Hkv, g, Dh] (GQA-shared selection)."""
    b, _, h, dh = inp.qr.shape
    hkv = inp.n_kv_heads
    return inp.qr[:, 0].reshape(b, hkv, h // hkv, dh)


def _unify_scores(scores: jnp.ndarray) -> jnp.ndarray:
    """[B, Hkv, nb] -> [B, 1, nb]: the cross-head max — a block any head
    wants, every head attends (SelectionSchedule.unify_heads)."""
    return jnp.max(scores, axis=1, keepdims=True)


def _broadcast_heads(idx: jnp.ndarray, hkv: int) -> jnp.ndarray:
    """[B, 1, k] unified selection -> [B, Hkv, k] (the kernel contract)."""
    return jnp.broadcast_to(idx, (idx.shape[0], hkv, idx.shape[-1]))


@dataclasses.dataclass(frozen=True)
class GatePolicy:
    """The paper's learned AttnGate (default). Contiguous decode scores the
    Kg cache through the fused gate-select kernel; paged decode scores
    straight off ``kg_pages`` through the page table (no per-slot Kg
    gather on the Pallas paths)."""
    dense = False
    needs_gate = True
    needs_meta = False
    reads_full_kv = False

    def select(self, inp: SelectionInputs, cfg: ModelConfig, *,
               impl: str = "ref",
               max_selected: Optional[int] = None,
               unify_heads: bool = False) -> jnp.ndarray:
        from repro.core import attngate as ag
        from repro.kernels import ops
        qg = ag.gate_q(inp.gate_params, inp.q_nope, inp.pos, cfg.gate)[:, 0]
        n_valid = kc.visible_blocks(jnp.maximum(inp.new_len, 1),
                                    cfg.gate.block_size)
        if unify_heads:
            # the fused gate-select kernels score per head, so head
            # unification always takes the jnp scoring path (same math as
            # gate_select_ref, with the cross-head max before ranking)
            from repro.models.common import NEG_INF
            if inp.kg is not None:
                kg = inp.kg
            else:
                from repro.serve import paging as pg
                kg = pg.gather_kg(inp.kg_pages[inp.layer], inp.page_table)
            nb = kg.shape[2]
            scores = jnp.einsum("bhd,bhnd->bhn", qg.astype(jnp.float32),
                                kg.astype(jnp.float32)) \
                / math.sqrt(qg.shape[-1])
            vmask = jnp.arange(nb)[None, None] < n_valid[:, None, None]
            scores = _unify_scores(jnp.where(vmask, scores, NEG_INF))
            if cfg.gate.method == "threshold":
                scores = jax.nn.softmax(scores, axis=-1)
            idx, _ = sp.select_blocks(scores, n_valid, cfg.gate,
                                      max_selected)
            return _broadcast_heads(idx, inp.n_kv_heads)
        if inp.kg is not None:
            return ops.gate_select(qg, inp.kg, n_valid, cfg.gate,
                                   max_selected, impl=impl)
        # the gate reads this layer's Kg rows out of a slice of the stack
        # (P x Hkv x Dg, 1/ps of the layer's K pool): XLA keeps the slice
        # in on-chip memory, where the kernel's row-sized reads ran 1.8x
        # faster on a v5e than out of the 28-layer stack in HBM
        return ops.gate_select_paged(qg, inp.kg_pages[inp.layer],
                                     inp.page_table, n_valid, cfg.gate,
                                     max_selected, impl=impl)


@dataclasses.dataclass(frozen=True)
class QuestPolicy:
    """Training-free Quest selection (Tang et al., 2024): rank blocks by
    the q·k upper bound from per-block key min/max. Metadata comes from
    the INCREMENTAL selection-metadata cache (core.metacache): completed
    blocks were finalized when ``cur_len`` crossed their boundary, only
    the trailing partial block is recomputed per step from its one
    block-sized K-cache slice (contiguous) or its one physical page
    (paged) — O(block_size) per step, never an O(S) cache read and never
    a cache-sized paged gather. Bitwise-equal selections to
    ``QuestRecomputePolicy`` (the O(S) reference) by construction.
    Selection is GQA-group-shared (max-pooled bound) so it can drive the
    shared-sparsity block-sparse kernel."""
    dense = False
    needs_gate = False
    needs_meta = True
    reads_full_kv = False

    def select(self, inp: SelectionInputs, cfg: ModelConfig, *,
               impl: str = "ref",
               max_selected: Optional[int] = None,
               unify_heads: bool = False) -> jnp.ndarray:
        from repro.core import metacache as mc
        from repro.core import quest
        bs = cfg.gate.block_size
        if inp.meta_kmin is not None and inp.k_cache is not None:
            tmin, tmax, t_idx = mc.trailing_meta(inp.k_cache, inp.new_len,
                                                 bs)
            kmin, kmax = mc.overlay_trailing(inp.meta_kmin, inp.meta_kmax,
                                             tmin, tmax, t_idx)
        elif inp.kmin_pages is not None and inp.k_pages is not None:
            # metadata-sized gather through the page table (npt rows per
            # slot — block_size x smaller than the K cache; the analog of
            # paging.gather_kg on the gate's ref path)
            kmin = jnp.swapaxes(
                inp.kmin_pages[inp.layer, inp.page_table], 1, 2)
            kmax = jnp.swapaxes(
                inp.kmax_pages[inp.layer, inp.page_table], 1, 2)
            tmin, tmax, t_idx = mc.trailing_meta_paged(
                inp.k_pages, inp.layer, inp.page_table, inp.new_len, bs,
                k_scale=inp.k_scale_pages)
            kmin, kmax = mc.overlay_trailing(kmin, kmax, tmin, tmax, t_idx)
        else:
            raise ValueError(
                "QuestPolicy needs the selection-metadata cache: build the "
                "decode state with options (prefill(..., options=...)) so "
                "meta_kmin/meta_kmax (or the paged kmin/kmax pools) are "
                "threaded; QuestRecomputePolicy is the cache-free O(S) "
                "reference")
        n_valid = kc.visible_blocks(jnp.maximum(inp.new_len, 1), bs)
        scores = quest.quest_scores_grouped(_grouped_q(inp), kmin, kmax,
                                            n_valid)
        if unify_heads:
            idx, _ = sp.budget_select(_unify_scores(scores), n_valid,
                                      cfg.gate, max_selected)
            return _broadcast_heads(idx, inp.n_kv_heads)
        idx, _ = sp.budget_select(scores, n_valid, cfg.gate, max_selected)
        return idx


@dataclasses.dataclass(frozen=True)
class QuestRecomputePolicy:
    """The pre-metacache Quest wiring: per-block key min/max REBUILT from
    the entire (post-rope) K cache every step — an O(S) read, plus a
    cache-sized gather on the paged path. Kept as the bitwise parity
    reference for ``QuestPolicy`` and as the honest 'what Quest costs
    without an incremental metadata cache' baseline in the ``policies``
    benchmark sweep. Not a serving policy."""
    dense = False
    needs_gate = False
    needs_meta = False
    reads_full_kv = True

    def select(self, inp: SelectionInputs, cfg: ModelConfig, *,
               impl: str = "ref",
               max_selected: Optional[int] = None,
               unify_heads: bool = False) -> jnp.ndarray:
        from repro.core import quest
        bs = cfg.gate.block_size
        k_view = _gathered_k(inp)
        kmin, kmax = quest.quest_meta_decode(k_view, inp.new_len, bs)
        n_valid = kc.visible_blocks(jnp.maximum(inp.new_len, 1), bs)
        scores = quest.quest_scores_grouped(_grouped_q(inp), kmin, kmax,
                                            n_valid)
        if unify_heads:
            idx, _ = sp.budget_select(_unify_scores(scores), n_valid,
                                      cfg.gate, max_selected)
            return _broadcast_heads(idx, inp.n_kv_heads)
        idx, _ = sp.budget_select(scores, n_valid, cfg.gate, max_selected)
        return idx


@dataclasses.dataclass(frozen=True)
class OraclePolicy:
    """Exact top-k over the true block row-max attention scores
    (core.oracle, paper §4.2): compute attention scores twice — once dense
    for ranking, once block-sparse. The accuracy ceiling of any selector
    (and at full budget, exactly dense attention's token set)."""
    dense = False
    needs_gate = False
    needs_meta = False
    reads_full_kv = True

    def select(self, inp: SelectionInputs, cfg: ModelConfig, *,
               impl: str = "ref",
               max_selected: Optional[int] = None,
               unify_heads: bool = False) -> jnp.ndarray:
        from repro.core import oracle
        bs = cfg.gate.block_size
        scores = oracle.oracle_scores_headmajor(
            _grouped_q(inp), _gathered_k(inp), inp.new_len, bs)
        n_valid = kc.visible_blocks(jnp.maximum(inp.new_len, 1), bs)
        if unify_heads:
            idx, _ = sp.budget_select(_unify_scores(scores), n_valid,
                                      cfg.gate, max_selected)
            return _broadcast_heads(idx, inp.n_kv_heads)
        idx, _ = sp.budget_select(scores, n_valid, cfg.gate, max_selected)
        return idx


@dataclasses.dataclass(frozen=True)
class DensePolicy:
    """No selection: full dense decode attention (the old ``sparse=False``)."""
    dense = True
    needs_gate = False
    needs_meta = False
    reads_full_kv = True

    def select(self, inp: SelectionInputs, cfg: ModelConfig, *,
               impl: str = "ref",
               max_selected: Optional[int] = None,
               unify_heads: bool = False) -> jnp.ndarray:
        raise NotImplementedError("DensePolicy performs no block selection")


@dataclasses.dataclass(frozen=True)
class SlidingWindowPolicy:
    """StreamingLM-style static pattern: ``sink_blocks`` leading blocks
    plus the trailing local window, no scoring and no extra state. The
    window width is the selection budget minus the sinks, so every policy
    compares at an equal block budget.

    Slot ORDER matters: the trailing (current-token) block comes FIRST,
    then the sinks, then the rest of the window — so a runtime budget
    mask (serve()'s per-request override truncates the list tail) can
    never drop the force-selected trailing block, mirroring the
    scored policies where forced blocks rank ahead of everything."""
    sink_blocks: int = 1
    dense = False
    needs_gate = False
    needs_meta = False
    reads_full_kv = False

    def __post_init__(self):
        if self.sink_blocks < 0:
            raise ValueError(f"sink_blocks must be >= 0: {self.sink_blocks}")

    def select(self, inp: SelectionInputs, cfg: ModelConfig, *,
               impl: str = "ref",
               max_selected: Optional[int] = None,
               unify_heads: bool = False) -> jnp.ndarray:
        # unify_heads is a no-op here: the pattern is position-only, so
        # every KV head already gets the identical row
        bs = cfg.gate.block_size
        nb = inp.n_blocks(bs)
        k = min(sp.resolve_max_selected(cfg.gate, max_selected), nb)
        # clamp visible_blocks (CEIL of new_len/bs) to the view's nb
        # (FLOOR of the cache length): on a non-block-aligned contiguous
        # cache the trailing partial block has no slot in the view, and an
        # unclamped ceil would point the window past it — the same clamp
        # rule quest.build_quest_meta applies (PR 5)
        n_valid = jnp.minimum(
            kc.visible_blocks(jnp.maximum(inp.new_len, 1), bs), nb)  # [B]
        sink = min(self.sink_blocks, max(k - 1, 0))
        ar = jnp.arange(k)[None, :]                               # [1, k]
        last = n_valid[:, None] - 1
        # slot 0: trailing block; slots [1, 1+sink]: sink blocks; rest:
        # the window continuing backwards from last-1
        idx = jnp.where(ar == 0, last,
                        jnp.where(ar <= sink, ar - 1, last - (ar - sink)))
        valid = (idx >= 0) & (idx < n_valid[:, None])
        # duplicates: a sink slot that IS the trailing block (tiny
        # context), and window entries falling into the sink region
        valid &= ~((ar >= 1) & (ar <= sink) & (idx == last))
        valid &= ~((ar > sink) & (idx < sink))
        idx = jnp.where(valid, idx, -1).astype(jnp.int32)
        return jnp.broadcast_to(idx[:, None, :],
                                (idx.shape[0], inp.n_kv_heads, k))


def selection_width(policy: SelectionPolicy, cfg: ModelConfig, nb: int,
                    max_selected: Optional[int] = None) -> int:
    """STATIC width k of the [B, Hkv, k] index list ``policy.select`` will
    return for an ``nb``-block view — the plan-buffer width a
    SelectionSchedule carries through the layer loop.

    Mirrors the per-policy width rules so the carried plan and a fresh
    selection always shape-match:
      * SlidingWindowPolicy: min(budget, nb) — no forced-block floor (the
        trailing block is slot 0 by construction; see its docstring and
        the width note in tests/test_policy.py)
      * GatePolicy under method='threshold': min(budget, nb)
        (sparsity.threshold_select applies no floor)
      * everything else (budget_select / the fused kernel's n_selected):
        min(max(budget, forced_floor), nb)
    """
    k = sp.resolve_max_selected(cfg.gate, max_selected)
    if isinstance(policy, SlidingWindowPolicy):
        return min(k, nb)
    if isinstance(policy, GatePolicy) and cfg.gate.method == "threshold":
        return min(k, nb)
    min_k = int(cfg.gate.always_last_block) + int(cfg.gate.always_first_block)
    return min(max(k, min_k), nb)


POLICIES: Dict[str, Any] = {
    "gate": GatePolicy,
    "quest": QuestPolicy,                     # incremental metadata cache
    "quest_cached": QuestPolicy,              # explicit alias
    "quest_recompute": QuestRecomputePolicy,  # O(S) parity/cost reference
    "oracle": OraclePolicy,
    "dense": DensePolicy,
    "sliding_window": SlidingWindowPolicy,
}


def get_policy(name: str, **kw) -> SelectionPolicy:
    """Policy by registry name (benchmark sweeps / CLI flags)."""
    try:
        return POLICIES[name](**kw)
    except KeyError:
        raise ValueError(
            f"unknown policy {name!r}; have {sorted(POLICIES)}") from None


@dataclasses.dataclass(frozen=True)
class DecodeOptions:
    """Frozen (hashable, jit-static) decode-time options: the single object
    threaded engine -> ModelApi -> model -> kernels.

    policy:          block-selection strategy (see module docstring)
    kernel_impl:     attention/selection execution path. None (default)
                     follows the platform (``platform_kernel_impl``: the
                     compiled Pallas kernels on a TPU, jnp elsewhere);
                     named paths: "ref" (jnp), "pallas" (TPU only — any
                     other backend raises at engine construction),
                     "pallas_interpret" (CPU kernel check), "sharded"
                     (shard_map over a mesh; GatePolicy or DensePolicy
                     only, needs a mesh-aware ``shard``; its per-shard
                     kernels follow the platform)
    sampling:        SamplingParams (default greedy — bitwise argmax)
    budget_override: token budget replacing ``cfg.gate.token_budget`` for
                     this options object (None = config budget); engines
                     additionally take cheaper PER-REQUEST budgets at serve
                     time (runtime-masked, no recompilation)
    measure_sparsity: compute measured selection telemetry (aux) inside
                     the decode step. Tiny per-layer reductions; set False
                     to compile them out of a throughput-critical loop
                     (the engine then reports ``measured=False``)
    split_k:         paged x sharded decode only (kernel_impl="sharded" on
                     the paged engine): reduce each head shard's selected
                     list in ``split_k`` independent flash partials
                     (kernels.block_sparse_decode_paged_splitk). 1 = the
                     single-pass path, bitwise identical to unsharded.
    schedule:        step-level SelectionSchedule (cross-layer plan reuse
                     + cross-head unification). The default (trivial)
                     schedule selects in every layer per head — the
                     bitwise-pinned pre-schedule behavior.
    track_evictions: paged decode only — emit a per-step ``touched_pages``
                     [n_slots, npt] bool aux (which logical blocks any
                     layer/head attended to) and clamp K/V page-table
                     reads into the physical pool, so the serving engine
                     can run RaaS page eviction with optimistic
                     execution + replay (ISSUE 7). Off by default: it is
                     a separate jit program.
    quantize:        paged decode only — page-pool precision. None (the
                     default) keeps fp pools and takes the original code
                     path verbatim (``tests/golden_policy.npz`` stays
                     bitwise). "int8" allocates int8 K/V page pools with
                     per-page per-head float32 scale rows (metacache
                     pattern: one row per page, swapped/evicted
                     alongside); dequant is fused into the block-sparse
                     decode kernels — no materialized fp copy of any
                     cache-sized array (ISSUE 9).
    """
    policy: SelectionPolicy = GatePolicy()
    kernel_impl: Optional[str] = None
    sampling: SamplingParams = GREEDY
    budget_override: Optional[int] = None
    measure_sparsity: bool = True
    split_k: int = 1
    schedule: SelectionSchedule = SelectionSchedule()
    track_evictions: bool = False
    quantize: Optional[str] = None

    def __post_init__(self):
        if self.quantize not in (None, "int8"):
            raise ValueError(
                f"quantize must be None or 'int8': {self.quantize!r}")
        if self.kernel_impl is not None and \
                self.kernel_impl not in KERNEL_IMPLS:
            raise ValueError(f"kernel_impl {self.kernel_impl!r} not in "
                             f"{KERNEL_IMPLS}")
        if self.split_k < 1:
            raise ValueError(f"split_k must be >= 1: {self.split_k}")
        if self.split_k > 1 and self.kernel_impl != "sharded":
            raise ValueError("split_k applies to the paged sharded path "
                             "(kernel_impl='sharded') only")
        if self.budget_override is not None and self.budget_override <= 0:
            raise ValueError(
                f"budget_override must be positive: {self.budget_override}")
        if self.kernel_impl == "sharded" and not isinstance(
                self.policy, (GatePolicy, DensePolicy)):
            raise ValueError("kernel_impl='sharded' supports GatePolicy "
                             "(distributed gate top-k) or DensePolicy only")
        if not self.schedule.is_trivial and self.policy.dense:
            raise ValueError("a non-trivial SelectionSchedule is "
                             "meaningless under DensePolicy (no selection "
                             "to schedule)")
        if self.kernel_impl == "sharded" and (
                self.schedule.dense_first_n > 0 or self.schedule.unify_heads
                or (self.schedule.select_layer or 0) > 0):
            raise ValueError(
                "kernel_impl='sharded' supports plan REUSE schedules only "
                "(select_layer=0 + correction_layers, per-head selection): "
                "the shard_map decode body always runs block-sparse "
                "attention, so no layer may stage DENSE. dense-prefix, "
                "select_layer>0 and unify_heads schedules need "
                "kernel_impl='ref'/'pallas'")
        if self.track_evictions and getattr(self.policy, "reads_full_kv",
                                            True):
            raise ValueError(
                "track_evictions (RaaS page eviction) requires a policy "
                "that only reads SELECTED blocks' K/V "
                f"(reads_full_kv=False); {type(self.policy).__name__} "
                "reads the full cache, so evicted pages would be silently "
                "read as garbage")
        if self.track_evictions and (
                self.schedule.dense_first_n > 0
                or (self.schedule.select_layer or 0) > 0):
            raise ValueError(
                "track_evictions cannot run with a schedule that stages "
                "any layer DENSE (dense_first_n > 0 or select_layer > 0): "
                "DENSE-staged layers read every visible block, so every "
                "evicted page would fault every step (evict/restore "
                "thrash)")

    @property
    def impl(self) -> str:
        """``kernel_impl`` with None resolved to the platform's kernels."""
        return self.kernel_impl or platform_kernel_impl()

    def check_platform(self) -> None:
        """Refuse the compiled TPU kernels on any other backend — never
        a quiet fallback to interpret mode or to the jnp path."""
        backend = jax.default_backend()
        if self.kernel_impl == "pallas" and backend != "tpu":
            raise ValueError(
                f"kernel_impl='pallas' runs the compiled TPU kernels, but "
                f"the default backend is {backend!r}; name "
                f"'pallas_interpret' (interpret-mode kernel check) or "
                f"'ref' (jnp), or leave kernel_impl=None for the "
                f"platform's own path")

    def max_selected(self, cfg: ModelConfig) -> Optional[int]:
        """Selected-list width override in BLOCKS (None = config budget).

        CEIL division: a budget_override that is not a multiple of the
        block size rounds UP, so the request never receives fewer tokens
        of attention than it asked for (a 100-token override at block 64
        buys 2 blocks = 128 tokens, not 1 block = 64). The CONFIG budget
        (sparsity.resolve_max_selected) intentionally keeps floor — see
        the rationale there."""
        if self.budget_override is None:
            return None
        return max(1, -(-self.budget_override // cfg.gate.block_size))

    def replace(self, **kw) -> "DecodeOptions":
        return dataclasses.replace(self, **kw)


def default_options(cfg: ModelConfig) -> DecodeOptions:
    """GatePolicy when the config carries a gate, dense otherwise — the
    old ``sparse=cfg.gate.enabled`` default. ``cfg.gate.dense_first_layers``
    (the paper's §5.2 hybrid dense layers, previously a config-only knob)
    maps onto the schedule's dense prefix; 0 keeps the trivial
    (bitwise-pinned) schedule."""
    gate_on = cfg.gate.enabled and cfg.has_attention and cfg.is_decoder
    if not gate_on:
        return DecodeOptions(policy=DensePolicy())
    return DecodeOptions(policy=GatePolicy(), schedule=SelectionSchedule(
        dense_first_n=cfg.gate.dense_first_layers))


DENSE_OPTIONS = DecodeOptions(policy=DensePolicy())


# -- SLO tiers (ISSUE 8) -----------------------------------------------------
#
# A tenant tier maps onto the serving engine's RUNTIME-MASKABLE knobs only
# — per-request token budget (a per-slot mask over the selected-block
# list), per-request SamplingParams (host-side sampler), per-request
# reserve admission, and scheduler priority. None of these touch the
# jitted step's static arguments, so EVERY tier shares one compiled
# program per serve() call: the tier -> options mapping is jit-static by
# construction. Anything that WOULD recompile (policy class, kernel impl,
# schedule) deliberately has no per-tier field.

@dataclasses.dataclass(frozen=True)
class TierSpec:
    """One tenant tier's serving contract.

    priority:  admission order (higher first; FIFO within a tier) AND
               preemption/eviction protection (victims are picked lowest
               priority first — a latency-tier request is never preempted
               or page-evicted while a throughput-tier victim exists).
    admission: "reserve" pins the request's full-lifetime page budget at
               admission (it can never stall mid-decode; the latency
               contract), "lazy" admits on current occupancy and grows
               on demand (the throughput contract — more concurrency,
               preemptible).
    budget:    per-request token budget override (runtime mask; None =
               the engine options' budget). Latency tiers typically run
               dense-ish (large budget), throughput tiers aggressively
               sparse (small budget).
    sampling:  per-request SamplingParams (None = engine default).
    """
    name: str = "default"
    priority: int = 0
    admission: str = "lazy"
    budget: Optional[int] = None
    sampling: Optional[SamplingParams] = None

    def __post_init__(self):
        if self.admission not in ("lazy", "reserve"):
            raise ValueError(f"tier {self.name!r}: admission "
                             f"{self.admission!r} not in ('lazy', 'reserve')")
        if self.budget is not None and self.budget <= 0:
            raise ValueError(f"tier {self.name!r}: budget must be positive: "
                             f"{self.budget}")

    def request_fields(self) -> dict:
        """The per-request dict fields the serving engine understands —
        merge into a request dict to place it in this tier."""
        out = {"tier": self.name, "priority": self.priority,
               "reserve": self.admission == "reserve"}
        if self.budget is not None:
            out["budget"] = self.budget
        if self.sampling is not None:
            out["sampling"] = self.sampling
        return out


class TierPolicy:
    """tier name -> TierSpec registry with a default fallback.

    ``apply(request_dict, tier)`` returns a NEW request dict carrying the
    tier's engine fields; explicit per-request overrides in the input
    dict win over the tier (a caller can still hand-tune one request).
    """

    def __init__(self, tiers: Sequence[TierSpec] = (),
                 default: Optional[TierSpec] = None):
        self.default = default if default is not None else TierSpec()
        self.tiers: Dict[str, TierSpec] = {t.name: t for t in tiers}
        if len(self.tiers) != len(tiers):
            names = [t.name for t in tiers]
            raise ValueError(f"duplicate tier names: {sorted(names)}")

    def get(self, name: Optional[str]) -> TierSpec:
        if name is None:
            return self.default
        try:
            return self.tiers[name]
        except KeyError:
            raise ValueError(f"unknown tier {name!r}; have "
                             f"{sorted(self.tiers)}") from None

    def apply(self, request: dict, tier: Optional[str] = None) -> dict:
        spec = self.get(tier if tier is not None else request.get("tier"))
        merged = dict(spec.request_fields())
        merged.update({k: v for k, v in request.items() if k != "tier"})
        merged["tier"] = spec.name
        return merged


def default_tiers(cfg: ModelConfig) -> TierPolicy:
    """The two-tier split the paper's serving story implies: a
    latency-critical tier (reserved pages, priority, near-dense budget)
    and a best-effort throughput tier (lazy admission, preemptible,
    aggressive sparsity). Budgets scale with the config's token budget so
    the tiers stay meaningful across reduced test configs."""
    base = max(cfg.gate.token_budget, cfg.gate.block_size)
    return TierPolicy(tiers=(
        TierSpec(name="latency", priority=10, admission="reserve",
                 budget=4 * base),
        TierSpec(name="throughput", priority=0, admission="lazy",
                 budget=base),
    ), default=TierSpec())
