"""Block-sparse flash decoding — Pallas TPU kernel (paper §3.3, TPU-native).

The paper's TileLang/H100 kernel walks a per-(batch, kv-head) list of
selected KV block indices, skipping all other KV-cache reads (decode is
I/O-bound, so at sparsity rho the speedup approaches 1/(1-rho)).

TPU adaptation (see DESIGN.md §2):
  * the selected-block index array is delivered via scalar prefetch
    (``PrefetchScalarGridSpec``) so each grid step's ``BlockSpec.index_map``
    can pick which KV block to stream HBM->VMEM — the TPU analog of the GPU
    gather. Only selected blocks ever leave HBM.
  * the GQA query group is padded to the sublane tile (>=16 rows for bf16)
    — the analog of the paper padding query-head groups to 64 for wgmma.
  * grid = (batch, heads_kv, ceil(nsel / C)); TPU grid iteration is
    sequential per core, so the online-softmax state (m, l, acc) lives in
    VMEM scratch across the block loop. Cross-chip split-K (the analog of
    the paper's num_split load balancing) is done one level up via
    sequence-sharded shard_map (repro.serve.sharded).
  * Mosaic double-buffers the HBM->VMEM streams, so the K/V fetch of the
    next grid step overlaps the MXU dots of the current one
    (warp-specialization analog).

Multi-block grid steps (ISSUE 2): each grid step folds ``C =
blocks_per_step`` selected blocks — C KV tiles ([C*bs, Dh] of KV bytes per
step) are streamed and folded into ONE online-softmax state update, so the
padded query tile amortizes over C-x larger KV reads and the grid / DMA
bookkeeping overhead drops ~C-x. ``nsel`` is padded to a multiple of C
with -1 (ignored) entries.

Layouts (NATIVE head-major — the decode-path invariant: no cache-sized
transpose or copy between token-in and logits-out; prefill does the
one-time layout conversion):
  q             [B, Hkv, G_pad, Dh]
  k_cache/v_...  [B, Hkv, S, Dh]     (S = nb * bs; contiguous block reads)
  k_pages/v_...  [L, P, Hkv, ps, Dh] (layer-stacked paged pools, ps ==
                                     block_size, read at ``layer``)
  layer         [] int32            (paged: scalar-prefetched; the K/V
                                     BlockSpecs squeeze the layer dim)
  block_indices [B, Hkv, nsel] int32 (-1 padding)
  kv_len        [B] int32
  out           [B, Hkv, G_pad, Dh]

Fused dequant (ISSUE 9): optional ``k_scales``/``v_scales`` — per-block
f32 dequant factors ([B, Hkv, nb] contiguous, [L, P, Hkv, 1] paged pool
rows). The scales of the SELECTED blocks are gathered outside the kernel
into a [B, Hkv, nsel] array laid out like the block indices (a
selection-sized gather, so the scalar-memory operand does not grow with
the pool), which rides the same scalar-prefetch path as the indices; the
kernel multiplies each streamed block by its scalar scale right after the
VMEM load's fp32 upcast, inside the online-softmax block loop. The
int8->fp conversion therefore only ever exists as one [bs, Dh] VMEM tile
per grid step — no fp copy of the cache is materialized, and HBM traffic
shrinks with the storage (~4x for int8 vs f32). ``None`` scales leave the
fp path byte-for-byte unchanged. (Real-TPU note: int8 VMEM tiles want a
(32, 128) min tile, so page_size >= 32 on hardware; interpret/ref modes
accept any size.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import F32, qk_precision   # the ref's dot precisions

NEG_INF = -1e30
LANES = 128


def out_vma(*xs) -> frozenset:
    """The mesh axes a kernel's output varies over: those of its operands.
    Inside a ``shard_map`` (with its replication check on) a Pallas output
    must declare them; elsewhere the set is empty and unused."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _flash_accum(idxs, b, j, len_ref, q_ref, k_refs, v_refs,
                 m_ref, l_ref, acc_ref, *, block_size: int, scale: float,
                 k_scales=None, v_scales=None):
    """Shared online-softmax accumulation: init scratch at ``j == 0``,
    fold ``C`` selected blocks in one state update (individual -1 padding
    blocks are masked out; a fully-padded group is skipped). Finalization
    is the caller's: normalize-and-write (``_flash_group``) or emit the
    raw (acc, m, l) partial (split-K kernel). ``k_scales``/``v_scales``:
    optional per-block scalar dequant factors (fused int8 dequant — the
    multiply rides the existing fp32 upcast of each streamed tile)."""
    C = len(k_refs)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gmax = idxs[0]
    for blk in idxs[1:]:
        gmax = jnp.maximum(gmax, blk)

    @pl.when(gmax >= 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                    # [G_pad, Dh]
        qk_prec = qk_precision(q_ref.dtype, k_refs[0].dtype,
                               k_scales is not None)
        scores = []
        for i in range(C):
            k = k_refs[i][0, 0].astype(jnp.float32)            # [bs, Dh]
            if k_scales is not None:
                k = k * k_scales[i]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    precision=qk_prec,
                                    preferred_element_type=jnp.float32) * scale
            pos = idxs[i] * block_size + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            # mask -1 padding blocks AND the partial trailing block
            s = jnp.where((idxs[i] >= 0) & (pos < len_ref[b]), s, NEG_INF)
            scores.append(s)
        m_prev = jnp.max(m_ref[...], axis=1, keepdims=True)    # [G_pad, 1]
        l_prev = jnp.max(l_ref[...], axis=1, keepdims=True)
        m_new = m_prev
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev
        acc = acc_ref[...] * alpha
        for i in range(C):
            # guard: a fully-masked block would give exp(NEG_INF-NEG_INF)=1
            p = jnp.where(scores[i] > NEG_INF / 2,
                          jnp.exp(scores[i] - m_new), 0.0)     # [G_pad, bs]
            l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
            v = v_refs[i][0, 0].astype(jnp.float32)
            if v_scales is not None:
                v = v * v_scales[i]
            acc = acc + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())), precision=F32,
                preferred_element_type=jnp.float32)
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _flash_group(idxs, b, j, n_groups, len_ref, q_ref, k_refs, v_refs,
                 o_ref, m_ref, l_ref, acc_ref, *, block_size: int,
                 scale: float, k_scales=None, v_scales=None):
    """Accumulate one group, normalize-and-write on the last grid step."""
    _flash_accum(idxs, b, j, len_ref, q_ref, k_refs, v_refs, m_ref, l_ref,
                 acc_ref, block_size=block_size, scale=scale,
                 k_scales=k_scales, v_scales=v_scales)

    @pl.when(j == n_groups - 1)
    def _finalize():
        l = jnp.max(l_ref[...], axis=1, keepdims=True)
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _kernel_body(idx_ref, len_ref, refs, *, block_size: int, n_groups: int,
                 blocks_per_step: int, scale: float, scale_refs=None):
    """Unpack the (q, k*C, v*C, o, scratch) ref layout and run one group.
    ``scale_refs`` = (ks_ref, vs_ref): the streamed blocks' dequant
    factors, laid out like ``idx_ref`` (quantized caches only)."""
    C = blocks_per_step
    q_ref = refs[0]
    k_refs = refs[1:1 + C]
    v_refs = refs[1 + C:1 + 2 * C]
    o_ref = refs[1 + 2 * C]
    m_ref, l_ref, acc_ref = refs[2 + 2 * C:5 + 2 * C]
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    idxs = [idx_ref[b, h, j * C + i] for i in range(C)]
    k_scales = v_scales = None
    if scale_refs is not None:
        ks_ref, vs_ref = scale_refs
        k_scales = [ks_ref[b, h, j * C + i] for i in range(C)]
        v_scales = [vs_ref[b, h, j * C + i] for i in range(C)]
    _flash_group(idxs, b, j, n_groups, len_ref, q_ref, k_refs, v_refs,
                 o_ref, m_ref, l_ref, acc_ref, block_size=block_size,
                 scale=scale, k_scales=k_scales, v_scales=v_scales)


def _kernel(idx_ref, len_ref,              # scalar prefetch
            *refs, **kw):
    _kernel_body(idx_ref, len_ref, refs, **kw)


def _kernel_quant(idx_ref, len_ref, ks_ref, vs_ref,  # scalar prefetch
                  *refs, **kw):
    # fused-dequant body: ks/vs [B, Hkv, nsel] hold the scale of each
    # SELECTED block (gathered outside the kernel, ``_selected_scales``),
    # so each streamed block's scale is a scalar read at its list slot
    _kernel_body(idx_ref, len_ref, refs, scale_refs=(ks_ref, vs_ref), **kw)


def _kernel_paged(layer_ref, idx_ref, pt_ref, len_ref,  # scalar prefetch
                  *refs, **kw):
    # identical math to _kernel — the layer and logical->physical
    # translation live entirely in the BlockSpec index_map (layer_ref and
    # pt_ref are consumed there); the in-kernel masking stays in LOGICAL
    # positions so kv_len semantics match the contiguous kernel exactly.
    _kernel_body(idx_ref, len_ref, refs, **kw)


def _kernel_paged_quant(layer_ref, idx_ref, pt_ref, len_ref, ks_ref, vs_ref,
                        *refs, **kw):
    _kernel_body(idx_ref, len_ref, refs, scale_refs=(ks_ref, vs_ref), **kw)


def _selected_scales(rows: jnp.ndarray, layer: jnp.ndarray,
                     ids: jnp.ndarray) -> jnp.ndarray:
    """Stacked per-head pool scale rows ``[L, P, Hkv, 1]`` at ``layer`` ->
    the scale of each listed page ``[B, Hkv, n]`` for physical ids
    ``[B, Hkv, n]``. A selection-sized gather: the scalar-prefetch operand
    then scales with the selected list, not with the pool — a pool-sized
    [P, Hkv] SMEM operand overflows the TPU's scalar memory at serving
    pool sizes."""
    hkv = ids.shape[1]
    return rows[layer, jnp.maximum(ids, 0), jnp.arange(hkv)[None, :, None],
                0].astype(jnp.float32)


def _layer_operand(layer) -> jnp.ndarray:
    """The layer index as the [1] int32 scalar-prefetch operand."""
    return jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))


def _physical_ids(block_indices: jnp.ndarray,
                  page_table: jnp.ndarray) -> jnp.ndarray:
    """Logical selected ids [B, Hkv, n] -> physical page ids (the same
    clamped translation the K/V index_maps do)."""
    b = block_indices.shape[0]
    phys = page_table[jnp.arange(b)[:, None, None],
                      jnp.maximum(block_indices, 0)]
    return jnp.maximum(phys, 0)


def _pad_group(g: int, dtype) -> int:
    base = 16 if jnp.dtype(dtype).itemsize <= 2 else 8
    return max(base, -(-g // base) * base)


def _pad_indices(block_indices: jnp.ndarray, nsel: int, blocks_per_step: int):
    """(C, n_groups, padded indices): nsel padded up to a multiple of C."""
    c = max(1, min(blocks_per_step, nsel))
    n_groups = -(-nsel // c)
    pad = n_groups * c - nsel
    if pad:
        b, hkv = block_indices.shape[:2]
        block_indices = jnp.concatenate(
            [block_indices,
             jnp.full((b, hkv, pad), -1, block_indices.dtype)], axis=-1)
    return c, n_groups, block_indices


@functools.partial(jax.jit, static_argnames=("block_size", "blocks_per_step",
                                             "interpret"))
def block_sparse_decode(q: jnp.ndarray, k_cache: jnp.ndarray,
                        v_cache: jnp.ndarray, block_indices: jnp.ndarray,
                        kv_len: jnp.ndarray, *, block_size: int,
                        blocks_per_step: int = 4,
                        interpret: bool = False,
                        k_scales: jnp.ndarray = None,
                        v_scales: jnp.ndarray = None) -> jnp.ndarray:
    """q [B,Hkv,G,Dh]; caches [B,Hkv,S,Dh] HEAD-MAJOR; indices [B,Hkv,nsel];
    kv_len [B]. The caches are consumed natively — no transpose.
    ``k_scales``/``v_scales`` [B, Hkv, nb] f32: per-block dequant factors
    for int8 caches, fused into the block loop (None = fp path verbatim)."""
    bsz, hkv, g, dh = q.shape
    nsel = block_indices.shape[-1]
    c, n_groups, idx = _pad_indices(block_indices, nsel, blocks_per_step)
    g_pad = _pad_group(g, q.dtype)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    scale = 1.0 / math.sqrt(dh)
    quant = k_scales is not None

    def q_map(b, h, j, *prefetch):
        return (b, h, 0, 0)

    def kv_map(i):
        def f(b, h, j, idx_ref, *rest):
            return (b, h, jnp.maximum(idx_ref[b, h, j * c + i], 0), 0)
        return f

    def o_map(b, h, j, *prefetch):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 if quant else 2,
        grid=(bsz, hkv, n_groups),
        in_specs=(
            [pl.BlockSpec((1, 1, g_pad, dh), q_map)]
            + [pl.BlockSpec((1, 1, block_size, dh), kv_map(i))
               for i in range(c)]
            + [pl.BlockSpec((1, 1, block_size, dh), kv_map(i))
               for i in range(c)]),
        out_specs=pl.BlockSpec((1, 1, g_pad, dh), o_map),
        scratch_shapes=[
            pltpu.VMEM((g_pad, LANES), jnp.float32),   # m
            pltpu.VMEM((g_pad, LANES), jnp.float32),   # l
            pltpu.VMEM((g_pad, dh), jnp.float32),      # acc
        ],
    )
    prefetch = [idx.astype(jnp.int32), kv_len.astype(jnp.int32)]
    if quant:
        bidx = jnp.arange(bsz)[:, None, None]
        hidx = jnp.arange(hkv)[None, :, None]
        safe = jnp.maximum(idx, 0)
        prefetch += [k_scales.astype(jnp.float32)[bidx, hidx, safe],
                     v_scales.astype(jnp.float32)[bidx, hidx, safe]]
    out = pl.pallas_call(
        functools.partial(_kernel_quant if quant else _kernel,
                          block_size=block_size, n_groups=n_groups,
                          blocks_per_step=c, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g_pad, dh), q.dtype,
                                       vma=out_vma(q, k_cache, v_cache)),
        interpret=interpret,
    )(*prefetch, qp, *([k_cache] * c), *([v_cache] * c))
    return out[:, :, :g]


@functools.partial(jax.jit, static_argnames=("block_size", "blocks_per_step",
                                             "interpret"))
def block_sparse_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                              v_pages: jnp.ndarray, layer: jnp.ndarray,
                              block_indices: jnp.ndarray,
                              page_table: jnp.ndarray, kv_len: jnp.ndarray,
                              *, block_size: int, blocks_per_step: int = 4,
                              interpret: bool = False,
                              k_scales: jnp.ndarray = None,
                              v_scales: jnp.ndarray = None) -> jnp.ndarray:
    """Paged variant: q [B,Hkv,G,Dh]; k_pages/v_pages [L, P, Hkv, ps, Dh]
    layer-STACKED HEAD-MAJOR global pools (ps == block_size), read at the
    int32 ``layer``; block_indices [B,Hkv,nsel] LOGICAL block ids (-1
    padding); page_table [B, npt] logical->physical.

    The layer index and the page table ride the same scalar-prefetch path
    as the selected indices, so the layer and logical->physical
    indirection happen inside the ``BlockSpec.index_map``: grid step
    (b, h, j) streams pages ``[layer, page_table[b,
    block_indices[b,h,j*C+i]], h]`` HBM->VMEM straight out of the stacked
    pool (its layer dim squeezed). Non-selected pages never leave HBM and
    no layer-sized slice of the pool is ever made — paging adds zero
    extra KV I/O.

    ``k_scales``/``v_scales`` [L, P, Hkv, 1] f32: per-page per-head
    dequant rows for int8 pools (serve.paging scale pools). The selected
    pages' scales are gathered at ``layer`` through the same
    logical->physical translation and ride scalar prefetch beside the
    indices (None = fp path verbatim).
    """
    bsz, hkv, g, dh = q.shape
    ps = k_pages.shape[3]
    assert ps == block_size, (ps, block_size)
    nsel = block_indices.shape[-1]
    c, n_groups, idx = _pad_indices(block_indices, nsel, blocks_per_step)
    g_pad = _pad_group(g, q.dtype)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    scale = 1.0 / math.sqrt(dh)
    quant = k_scales is not None

    def q_map(b, h, j, *prefetch):
        return (b, h, 0, 0)

    def kv_map(i):
        def f(b, h, j, layer_ref, idx_ref, pt_ref, *rest):
            log = jnp.maximum(idx_ref[b, h, j * c + i], 0)
            phys = pt_ref[b, log]
            return (layer_ref[0], jnp.maximum(phys, 0), h, 0, 0)
        return f

    def o_map(b, h, j, *prefetch):
        return (b, h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6 if quant else 4,
        grid=(bsz, hkv, n_groups),
        in_specs=(
            [pl.BlockSpec((1, 1, g_pad, dh), q_map)]
            + [pl.BlockSpec((None, 1, 1, ps, dh), kv_map(i))
               for i in range(c)]
            + [pl.BlockSpec((None, 1, 1, ps, dh), kv_map(i))
               for i in range(c)]),
        out_specs=pl.BlockSpec((1, 1, g_pad, dh), o_map),
        scratch_shapes=[
            pltpu.VMEM((g_pad, LANES), jnp.float32),   # m
            pltpu.VMEM((g_pad, LANES), jnp.float32),   # l
            pltpu.VMEM((g_pad, dh), jnp.float32),      # acc
        ],
    )
    prefetch = [_layer_operand(layer), idx.astype(jnp.int32),
                page_table.astype(jnp.int32), kv_len.astype(jnp.int32)]
    if quant:
        phys = _physical_ids(idx, page_table)
        prefetch += [_selected_scales(k_scales, layer, phys),
                     _selected_scales(v_scales, layer, phys)]
    out = pl.pallas_call(
        functools.partial(_kernel_paged_quant if quant else _kernel_paged,
                          block_size=block_size,
                          n_groups=n_groups, blocks_per_step=c, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hkv, g_pad, dh), q.dtype,
                                       vma=out_vma(q, k_pages, v_pages)),
        interpret=interpret,
    )(*prefetch, qp, *([k_pages] * c), *([v_pages] * c))
    return out[:, :, :g]


def _kernel_paged_splitk(layer_ref, idx_ref, pt_ref, len_ref,  # prefetch
                         *refs, block_size: int, n_groups: int,
                         blocks_per_step: int, scale: float, per_pad: int,
                         scale_refs=None):
    """Split-K body: each (b, h, s) lane accumulates its OWN split's
    online-softmax state and emits the raw partial (acc, m, l) instead of
    normalizing — the cross-split combine happens outside the kernel."""
    C = blocks_per_step
    q_ref = refs[0]
    k_refs = refs[1:1 + C]
    v_refs = refs[1 + C:1 + 2 * C]
    o_ref, mo_ref, lo_ref = refs[1 + 2 * C:4 + 2 * C]
    m_ref, l_ref, acc_ref = refs[4 + 2 * C:7 + 2 * C]
    b = pl.program_id(0)
    h = pl.program_id(1)
    s = pl.program_id(2)
    j = pl.program_id(3)
    slots = [s * per_pad + j * C + i for i in range(C)]
    idxs = [idx_ref[b, h, t] for t in slots]
    k_scales = v_scales = None
    if scale_refs is not None:
        ks_ref, vs_ref = scale_refs
        k_scales = [ks_ref[b, h, t] for t in slots]
        v_scales = [vs_ref[b, h, t] for t in slots]
    _flash_accum(idxs, b, j, len_ref, q_ref, k_refs, v_refs, m_ref, l_ref,
                 acc_ref, block_size=block_size, scale=scale,
                 k_scales=k_scales, v_scales=v_scales)

    @pl.when(j == n_groups - 1)
    def _emit_partial():
        o_ref[0, 0, 0] = acc_ref[...]
        mo_ref[0, 0, 0] = m_ref[...]
        lo_ref[0, 0, 0] = l_ref[...]


def _kernel_paged_splitk_quant(layer_ref, idx_ref, pt_ref, len_ref, ks_ref,
                               vs_ref, *refs, **kw):
    _kernel_paged_splitk(layer_ref, idx_ref, pt_ref, len_ref, *refs,
                         scale_refs=(ks_ref, vs_ref), **kw)


@functools.partial(jax.jit, static_argnames=("block_size", "num_splits",
                                             "blocks_per_step", "interpret"))
def block_sparse_decode_paged_splitk(q: jnp.ndarray, k_pages: jnp.ndarray,
                                     v_pages: jnp.ndarray,
                                     layer: jnp.ndarray,
                                     block_indices: jnp.ndarray,
                                     page_table: jnp.ndarray,
                                     kv_len: jnp.ndarray, *, block_size: int,
                                     num_splits: int = 2,
                                     blocks_per_step: int = 4,
                                     interpret: bool = False,
                                     k_scales: jnp.ndarray = None,
                                     v_scales: jnp.ndarray = None
                                     ) -> jnp.ndarray:
    """Split-K variant of ``block_sparse_decode_paged`` (the TPU analog of
    the paper's ``num_split`` SM load balancing, ISSUE 4).

    The selected-block list is split into ``num_splits`` segments that map
    to a third grid dimension, so Mosaic can pipeline the segments'
    HBM->VMEM streams independently; each segment emits an unnormalized
    flash partial (acc, m, l) and the partials merge with the two-pass
    rescale in jnp (exactly ``ref.paged_sparse_decode_splitk_ref``). Use
    when a single sequence's selected list is long enough to starve the
    grid — e.g. the paged x sharded serving path, where each head shard
    owns the full selected list of its local heads. Pools, ``layer`` and
    scales as ``block_sparse_decode_paged``.
    """
    bsz, hkv, g, dh = q.shape
    ps = k_pages.shape[3]
    assert ps == block_size, (ps, block_size)
    ns = max(1, num_splits)
    nsel = block_indices.shape[-1]
    per = -(-nsel // ns)                  # selected entries per split
    c = max(1, min(blocks_per_step, per))
    n_groups = -(-per // c)
    per_pad = n_groups * c                # per split, padded to C multiple
    bi = jnp.full((bsz, hkv, ns * per_pad), -1, block_indices.dtype)
    bi = bi.reshape(bsz, hkv, ns, per_pad).at[:, :, :, :per].set(
        jnp.pad(block_indices, ((0, 0), (0, 0), (0, per * ns - nsel)),
                constant_values=-1).reshape(bsz, hkv, ns, per))
    idx = bi.reshape(bsz, hkv, ns * per_pad)
    g_pad = _pad_group(g, q.dtype)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    scale = 1.0 / math.sqrt(dh)
    quant = k_scales is not None

    def q_map(b, h, s, j, *prefetch):
        return (b, h, 0, 0)

    def kv_map(i):
        def f(b, h, s, j, layer_ref, idx_ref, pt_ref, *rest):
            log = jnp.maximum(idx_ref[b, h, s * per_pad + j * c + i], 0)
            phys = pt_ref[b, log]
            return (layer_ref[0], jnp.maximum(phys, 0), h, 0, 0)
        return f

    def part_map(b, h, s, j, *prefetch):
        return (b, h, s, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6 if quant else 4,
        grid=(bsz, hkv, ns, n_groups),
        in_specs=(
            [pl.BlockSpec((1, 1, g_pad, dh), q_map)]
            + [pl.BlockSpec((None, 1, 1, ps, dh), kv_map(i))
               for i in range(c)]
            + [pl.BlockSpec((None, 1, 1, ps, dh), kv_map(i))
               for i in range(c)]),
        out_specs=(pl.BlockSpec((1, 1, 1, g_pad, dh), part_map),
                   pl.BlockSpec((1, 1, 1, g_pad, LANES), part_map),
                   pl.BlockSpec((1, 1, 1, g_pad, LANES), part_map)),
        scratch_shapes=[
            pltpu.VMEM((g_pad, LANES), jnp.float32),   # m
            pltpu.VMEM((g_pad, LANES), jnp.float32),   # l
            pltpu.VMEM((g_pad, dh), jnp.float32),      # acc
        ],
    )
    prefetch = [_layer_operand(layer), idx.astype(jnp.int32),
                page_table.astype(jnp.int32), kv_len.astype(jnp.int32)]
    if quant:
        phys = _physical_ids(idx, page_table)
        prefetch += [_selected_scales(k_scales, layer, phys),
                     _selected_scales(v_scales, layer, phys)]
    acc, m, l = pl.pallas_call(
        functools.partial(
            _kernel_paged_splitk_quant if quant else _kernel_paged_splitk,
            block_size=block_size,
            n_groups=n_groups, blocks_per_step=c, scale=scale,
            per_pad=per_pad),
        grid_spec=grid_spec,
        out_shape=tuple(
            jax.ShapeDtypeStruct((bsz, hkv, ns, g_pad, w), jnp.float32,
                                 vma=out_vma(q, k_pages, v_pages))
            for w in (dh, LANES, LANES)),
        interpret=interpret,
    )(*prefetch, qp, *([k_pages] * c), *([v_pages] * c))

    # cross-split combine (two-pass rescale; matches the split-K ref)
    m_s = m[..., :1]                                     # [B,Hkv,NS,G,1]
    l_s = l[..., :1]
    m_g = jnp.max(m_s, axis=2, keepdims=True)
    rescale = jnp.where(l_s > 0, jnp.exp(m_s - m_g), 0.0)
    l_g = jnp.sum(l_s * rescale, axis=2)                 # [B,Hkv,G,1]
    o = jnp.sum(acc * rescale, axis=2) / jnp.maximum(l_g, 1e-30)
    return o[:, :, :g].astype(q.dtype)
