"""Fused gate-score + block-selection — Pallas TPU kernel (ISSUE 2).

Replaces the decode-time XLA chain ``gate_logits (fp32 dense einsum) ->
visibility mask -> [softmax] -> force first/last -> jax.lax.top_k`` of
``transformer._gate_select`` with ONE kernel that reads the head-major
K-compression cache and emits the selected block index list directly:

  qg       [B, Hkv, Dg]      post-rope gate query of the new token
  kg       [B, Hkv, nb, Dg]  head-major Kg cache (contiguous or a paged
                             per-slot gather)
  n_valid  [B] int32         number of currently visible blocks
  -> idx   [B, Hkv, k] int32 selected LOGICAL block ids, -1 padding

Selection semantics are EXACTLY ``core.sparsity.select_blocks`` (both the
``budget`` top-k and the ``threshold`` softmax methods, including the
force-first/last pinning and -1 invalid padding): the jnp twin below is
bit-compatible with the pre-fusion chain, and the kernel reproduces
``jax.lax.top_k`` ordering (descending value, ties broken by lower index)
via iterative argmax — k is small (token_budget / block_size), so the
selection cost stays O(k * nb) per (batch, kv-head) and sublinear in
context, per the Sparse-Frontier selection-overhead discipline.

Grid = (B,); each step streams one slot's [Hkv, nb, Dg] Kg rows HBM->VMEM,
does the per-head [1, Dg] x [Dg, nb] score dots on-chip, ranks all heads
at once and never materialises the fp32 score tensor in HBM. Every block
spans the full last two dims of its array (the TPU (8, 128) tiling rule).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.config import GateConfig
from repro.core import sparsity as sp
from repro.kernels.block_sparse_decode import out_vma
from repro.models.common import NEG_INF

LANES = 128
PAGES_PER_STEP = 8      # Kg rows DMA'd per grid step of the paged kernel


def n_selected(cfg: GateConfig, nb: int,
               max_selected: Optional[int] = None) -> int:
    """Static selected-list width — ``sparsity.resolve_max_selected``
    (the shared cap rule) plus select_blocks' per-method floor/cap
    (budget floor for forced blocks, cap at nb)."""
    k = sp.resolve_max_selected(cfg, max_selected)
    if cfg.method == "budget":
        k = max(k, int(cfg.always_last_block) + int(cfg.always_first_block))
    elif cfg.method != "threshold":
        raise ValueError(cfg.method)
    return min(k, nb)


def gate_select_ref(qg: jnp.ndarray, kg: jnp.ndarray, n_valid: jnp.ndarray,
                    cfg: GateConfig, max_selected: Optional[int] = None
                    ) -> jnp.ndarray:
    """jnp twin: head-major gate scoring + ``select_blocks`` (the decode
    ground truth; also the CPU execution path)."""
    dg = qg.shape[-1]
    scores = jnp.einsum("bhd,bhnd->bhn", qg.astype(jnp.float32),
                        kg.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST) / math.sqrt(dg)
    nb = scores.shape[-1]
    vmask = jnp.arange(nb)[None, None] < n_valid[:, None, None]
    scores = jnp.where(vmask, scores, NEG_INF)
    if cfg.method == "threshold":
        scores = jax.nn.softmax(scores, axis=-1)
    idx, _ = sp.select_blocks(scores, n_valid, cfg, max_selected)
    return idx


def _rank_and_pick(s, nv, *, k_sel: int, method: str, threshold: float,
                   force_first: bool, force_last: bool):
    """Shared selection core of both kernels: visibility-masked scores
    ``s [H, nb]`` (one row per KV head) -> selected block ids ``[H, k_sel]``
    (-1 padding), with ``select_blocks`` semantics (force pinning,
    lax.top_k tie-breaking). Column ids ride in f32 (exact below 2^24) so
    every reduction is a plain f32 lane reduction on the TPU."""
    big = jnp.float32(1e30)
    nb = s.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    colf = col.astype(jnp.float32)

    if method == "threshold":
        # softmax over the UNFORCED masked logits (jax.nn.softmax form),
        # then threshold_select: invisible -> -1, force, admit > tau.
        m = jnp.max(s, axis=1, keepdims=True)
        e = jnp.exp(s - m)
        probs = e / jnp.sum(e, axis=1, keepdims=True)
        ranked = jnp.where(col < nv, probs, -1.0)
        if force_last:
            ranked = jnp.where(col == nv - 1, big, ranked)
        if force_first:
            ranked = jnp.where(col == 0, big, ranked)
        ranked = jnp.where(ranked > threshold, ranked, -1.0)
        cutoff = jnp.float32(0.0)
        drop = jnp.float32(-2.0)
    else:                                   # budget: top-k on raw logits
        ranked = s
        if force_last:
            ranked = jnp.where(col == nv - 1, big, ranked)
        if force_first:
            ranked = jnp.where(col == 0, big, ranked)
        cutoff = jnp.float32(NEG_INF / 2)
        drop = jnp.float32(2 * NEG_INF)

    # iterative exact top-k with lax.top_k tie-breaking (lower index first)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], k_sel), 1)
    out = jnp.full((s.shape[0], k_sel), -1.0, jnp.float32)
    for i in range(k_sel):
        m = jnp.max(ranked, axis=1, keepdims=True)                # [H, 1]
        pick = jnp.min(jnp.where(ranked == m, colf, float(nb)), axis=1,
                       keepdims=True)                             # [H, 1]
        out = jnp.where(kcol == i, jnp.where(m > cutoff, pick, -1.0), out)
        ranked = jnp.where(colf == pick, drop, ranked)
    return out.astype(jnp.int32)


def _select_kernel(nv_ref,                  # scalar prefetch
                   qg_ref, kg_ref,          # VMEM in [1,H,Dg], [1,H,nb,Dg]
                   o_ref,                   # VMEM out [1,H,k]
                   *, k_sel: int, method: str, threshold: float,
                   force_first: bool, force_last: bool, scale: float):
    b = pl.program_id(0)
    nv = nv_ref[b]
    hkv, nb = kg_ref.shape[1], kg_ref.shape[2]
    row = jax.lax.broadcasted_iota(jnp.int32, (hkv, nb), 0)
    s = jnp.zeros((hkv, nb), jnp.float32)
    for h in range(hkv):                    # one [1,Dg] x [nb,Dg] dot per head
        q = qg_ref[0, pl.ds(h, 1), :].astype(jnp.float32)       # [1, Dg]
        kg = kg_ref[0, h].astype(jnp.float32)                   # [nb, Dg]
        s_h = jax.lax.dot_general(q, kg, (((1,), (1,)), ((), ())),
                                  precision=jax.lax.Precision.HIGHEST,
                                  preferred_element_type=jnp.float32)
        s = jnp.where(row == h, s_h * scale, s)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(col < nv, s, NEG_INF)                        # visibility
    o_ref[0] = _rank_and_pick(
        s, nv, k_sel=k_sel, method=method, threshold=threshold,
        force_first=force_first, force_last=force_last)


@functools.partial(jax.jit, static_argnames=("cfg", "max_selected",
                                             "interpret"))
def fused_gate_select(qg: jnp.ndarray, kg: jnp.ndarray, n_valid: jnp.ndarray,
                      cfg: GateConfig, max_selected: Optional[int] = None,
                      interpret: bool = False) -> jnp.ndarray:
    """qg [B,Hkv,Dg]; kg [B,Hkv,nb,Dg] head-major; n_valid [B] int32
    -> block ids [B,Hkv,k] int32 (-1 padding), identical to the jnp twin.
    Grid = (B,): each step covers every KV head, so every block's last
    two dims are full array dims (the TPU (8, 128) tiling rule)."""
    b, hkv, dg = qg.shape
    nb = kg.shape[2]
    k_sel = n_selected(cfg, nb, max_selected)
    scale = 1.0 / math.sqrt(dg)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, hkv, dg), lambda bi, nv_ref: (bi, 0, 0)),
            pl.BlockSpec((1, hkv, nb, dg),
                         lambda bi, nv_ref: (bi, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, k_sel),
                               lambda bi, nv_ref: (bi, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(
            _select_kernel, k_sel=k_sel, method=cfg.method,
            threshold=float(cfg.threshold),
            force_first=bool(cfg.always_first_block),
            force_last=bool(cfg.always_last_block), scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, k_sel), jnp.int32,
                                       vma=out_vma(qg, kg)),
        interpret=interpret,
    )(n_valid.astype(jnp.int32), qg, kg)


# ---------------------------------------------------------------------------
# paged twin: gate-select straight off kg_pages (no per-slot Kg gather)
# ---------------------------------------------------------------------------

def gate_select_paged_ref(qg: jnp.ndarray, kg_pages: jnp.ndarray,
                          page_table: jnp.ndarray, n_valid: jnp.ndarray,
                          cfg: GateConfig, max_selected: Optional[int] = None
                          ) -> jnp.ndarray:
    """jnp twin (the semantic spec + CPU path): per-slot Kg gather through
    the page table (``serve.paging.gather_kg``, the same view the engine
    uses), then the contiguous selection. The gather is Kg-sized (<1% of
    KV), not cache-sized; the Pallas kernel below removes even that copy
    by streaming pages through a scalar-prefetch index_map."""
    from repro.serve.paging import gather_kg   # local: no kernels->serve cycle
    kg = gather_kg(kg_pages, page_table)               # [S, Hkv, npt, Dg]
    return gate_select_ref(qg, kg, n_valid, cfg, max_selected)


def _select_paged_kernel(pt_ref, nv_ref,    # scalar prefetch
                         qg_ref, *refs,     # VMEM in [1,H,Dg] each
                         k_sel: int, method: str,
                         threshold: float, force_first: bool,
                         force_last: bool, scale: float):
    # refs: C Kg rows [1,H,Dg] (one per table entry of this grid step),
    # then the output [1,H,k] and the lane-padded [H, >=npt] f32 scores
    c = len(refs) - 2
    kg_refs, o_ref, s_ref = refs[:c], refs[c], refs[c + 1]
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    q = qg_ref[0].astype(jnp.float32)                          # [H, Dg]
    col = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
    s = s_ref[...]
    for i in range(c):
        kg = kg_refs[i][0].astype(jnp.float32)                 # [H, Dg]
        s_i = jnp.sum(q * kg, axis=1, keepdims=True) * scale   # [H, 1]
        s = jnp.where(col == j * c + i, s_i, s)
    s_ref[...] = s

    @pl.when(j == pl.num_programs(1) - 1)
    def _select():
        nv = nv_ref[b]
        # columns past npt (lane padding) are never visible: nv <= npt
        s = jnp.where(col < nv, s_ref[...], NEG_INF)           # visibility
        o_ref[0] = _rank_and_pick(
            s, nv, k_sel=k_sel, method=method, threshold=threshold,
            force_first=force_first, force_last=force_last)


@functools.partial(jax.jit, static_argnames=("cfg", "max_selected",
                                             "interpret"))
def fused_gate_select_paged(qg: jnp.ndarray, kg_pages: jnp.ndarray,
                            page_table: jnp.ndarray, n_valid: jnp.ndarray,
                            cfg: GateConfig,
                            max_selected: Optional[int] = None,
                            interpret: bool = False) -> jnp.ndarray:
    """Paged fused gate-select: scores one layer's Kg pool rows DIRECTLY
    through the page table (the TPU analog of skipping ``gather_kg``).

    qg [S, Hkv, Dg] per-slot gate queries; kg_pages [P, Hkv, Dg] pooled Kg
    rows (one per physical page); page_table [S, npt] int32; n_valid [S].
    Grid = (S, ceil(npt / C)) with C = ``PAGES_PER_STEP``: each step DMAs
    C [Hkv, Dg] Kg rows — the rows of the pages the slot's table maps
    logical blocks j*C..j*C+C-1 to — scores them into a [Hkv, npt]
    scratch, and the last step runs the same ranked selection as the
    contiguous kernel. Table entries past the visible blocks re-map to the
    last visible page (an unchanged block index skips its DMA); their
    scores, like those of unallocated entries that point at the null page,
    are masked by the visibility cut (col < n_valid) before ranking.
    Returns logical ids [S, Hkv, k], -1 padding, identical to
    ``gate_select_paged_ref``.
    """
    s, hkv, dg = qg.shape
    npt = page_table.shape[1]
    k_sel = n_selected(cfg, npt, max_selected)
    scale = 1.0 / math.sqrt(dg)
    c = max(1, min(PAGES_PER_STEP, npt))
    n_steps = -(-npt // c)
    width = -(-n_steps * c // LANES) * LANES    # score scratch, lane-padded

    def kg_map(i):
        def f(bi, j, pt_ref, nv_ref):
            last = jnp.clip(nv_ref[bi] - 1, 0, npt - 1)
            return (pt_ref[bi, jnp.minimum(j * c + i, last)], 0, 0)
        return f

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, n_steps),
        in_specs=[pl.BlockSpec((1, hkv, dg),
                               lambda bi, j, pt_ref, nv_ref: (bi, 0, 0))]
        + [pl.BlockSpec((1, hkv, dg), kg_map(i)) for i in range(c)],
        out_specs=pl.BlockSpec((1, hkv, k_sel),
                               lambda bi, j, pt_ref, nv_ref: (bi, 0, 0)),
        scratch_shapes=[pltpu.VMEM((hkv, width), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(
            _select_paged_kernel, k_sel=k_sel, method=cfg.method,
            threshold=float(cfg.threshold),
            force_first=bool(cfg.always_first_block),
            force_last=bool(cfg.always_last_block), scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, k_sel), jnp.int32,
                                       vma=out_vma(qg, kg_pages)),
        interpret=interpret,
    )(page_table.astype(jnp.int32), n_valid.astype(jnp.int32), qg,
      *([kg_pages] * c))
