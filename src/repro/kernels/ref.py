"""Pure-jnp reference oracles for the Pallas kernels.

These are the correctness ground truth (kernels assert_allclose against
them) AND the execution path of every non-TPU backend
(``core.policy.platform_kernel_impl``).

Contracts (HEAD-MAJOR decode layouts — the decode-path invariant: no
cache-sized transpose or copy; every decode-time access below is a
selected-blocks-only gather off the native layout)
---------
sparse_decode_ref:
  q             [B, Hkv, G, Dh]   one new query token, grouped per kv head
  k_cache       [B, Hkv, S, Dh]   post-rope keys (S = nb * block_size)
  v_cache       [B, Hkv, S, Dh]
  block_indices [B, Hkv, nsel]    int32 selected block ids, -1 = padding
  kv_len        [B]               valid lengths (masks the partial last block)
  -> o          [B, Hkv, G, Dh]

gate_gt_attention_ref:
  q [B, Lq, H, Dh], k/v [B, Lk, Hkv, Dh]  (causal, optional segment ids)
  -> o [B, Lq, H, Dh], blockmax [B, H, Lq, nb] fp32 masked block row-max

Fused dequant (ISSUE 9): every decode ref takes optional
``k_scales``/``v_scales`` — per-block symmetric dequant factors (value =
stored * scale), [B, Hkv, nb] for the contiguous cache, [L, P, Hkv, 1]
pool rows for the paged twins. The scale multiply happens on the GATHERED
selected blocks only, inside the same fp32 upcast attention already does
— no cache-sized fp copy materializes, and ``None`` leaves the original
math verbatim (bitwise contract).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import NEG_INF

# Decode dot precision. On a TPU a DEFAULT-precision f32 matmul rounds its
# operands to bf16 (one MXU pass). That is exact for q.k when q and k are
# both bf16 values (the fp serving path), so that dot keeps the one pass;
# dequantized int8 keys, f32 caches and the f32 softmax weights of p.v
# need full f32 (HIGHEST). The Pallas kernels pick the same precisions, so
# the two paths differ only in f32 accumulation order. Precision is a
# no-op on the CPU.
F32 = jax.lax.Precision.HIGHEST


def qk_precision(q_dtype, k_dtype, dequantized: bool) -> jax.lax.Precision:
    """Precision of the q.k dot: one bf16 pass where it is exact (bf16 q
    and k, no dequant scale), full f32 otherwise."""
    exact = (not dequantized and jnp.dtype(q_dtype) == jnp.bfloat16
             and jnp.dtype(k_dtype) == jnp.bfloat16)
    return jax.lax.Precision.DEFAULT if exact else F32


def _deq(g: jnp.ndarray, scales: Optional[jnp.ndarray], idx: jnp.ndarray,
         block_size: int) -> jnp.ndarray:
    """Dequantize gathered blocks: g [..., nsel*bs, Dh] x per-selected-block
    scales gathered as [..., nsel] -> fp32. None = fp passthrough."""
    if scales is None:
        return g.astype(jnp.float32)
    shp = g.shape
    sel = jnp.take_along_axis(scales, idx, axis=-1)       # [..., nsel]
    g = g.reshape(shp[:-2] + (idx.shape[-1], block_size, shp[-1]))
    return (g.astype(jnp.float32) * sel[..., None, None]).reshape(shp)


def sparse_decode_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                      v_cache: jnp.ndarray, block_indices: jnp.ndarray,
                      kv_len: jnp.ndarray, *, block_size: int,
                      k_scales: Optional[jnp.ndarray] = None,
                      v_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    b, hkv, g, dh = q.shape
    nsel = block_indices.shape[-1]
    scale = 1.0 / math.sqrt(dh)

    idx = jnp.maximum(block_indices, 0)                          # [B,Hkv,nsel]
    # token positions of gathered blocks: [B,Hkv,nsel,bs]
    pos = idx[..., None] * block_size + jnp.arange(block_size)
    # gather selected keys/values straight off the head-major cache
    gpos = pos.reshape(b, hkv, nsel * block_size)
    kg = jnp.take_along_axis(k_cache, gpos[..., None], axis=2)   # [B,Hkv,n*bs,Dh]
    vg = jnp.take_along_axis(v_cache, gpos[..., None], axis=2)
    kg = _deq(kg, k_scales, idx, block_size)
    vg = _deq(vg, v_scales, idx, block_size)

    sc = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                    kg.astype(jnp.float32),
                    precision=qk_precision(q.dtype, k_cache.dtype,
                                           k_scales is not None)) * scale
    valid = (block_indices[..., None] >= 0) & (pos < kv_len[:, None, None, None])
    valid = valid.reshape(b, hkv, 1, nsel * block_size)
    sc = jnp.where(valid, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    # guard rows with zero valid keys (shouldn't happen: last block forced)
    p = jnp.where(jnp.any(valid, axis=-1, keepdims=True), p, 0.0)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, vg.astype(jnp.float32),
                   precision=F32)
    return o.astype(q.dtype)


def paged_sparse_decode_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                            v_pages: jnp.ndarray, layer: jnp.ndarray,
                            block_indices: jnp.ndarray,
                            page_table: jnp.ndarray, kv_len: jnp.ndarray, *,
                            block_size: int,
                            k_scales: Optional[jnp.ndarray] = None,
                            v_scales: Optional[jnp.ndarray] = None
                            ) -> jnp.ndarray:
    """Paged twin of ``sparse_decode_ref``.

    k_pages/v_pages: [L, P, Hkv, ps, Dh] layer-stacked head-major global
    pools (ps == block_size), read at the int32 ``layer`` index in the
    same gather as the pages; page_table: [B, npt] int32 logical block ->
    physical page; block_indices carry LOGICAL block ids (the gate's view)
    — the logical->physical indirection happens here, mirroring the
    kernel's lookup in its scalar-prefetched page table. The selected
    pages are gathered directly off the native pool layout (no
    pool-sized transpose); after the gather the math is kept identical
    to the contiguous reference so paged == contiguous holds to rounding.
    ``k_scales``/``v_scales`` [L, P, Hkv, 1] dequantize int8 pools on the
    gathered pages only (the scale row rides the same physical-page
    gather as its page).
    """
    b, hkv, g, dh = q.shape
    ps = k_pages.shape[3]
    assert ps == block_size, (ps, block_size)
    nsel = block_indices.shape[-1]
    scale = 1.0 / math.sqrt(dh)

    idx = jnp.maximum(block_indices, 0)                          # [B,Hkv,nsel]
    pt = jnp.broadcast_to(page_table[:, None, :],
                          (b, hkv, page_table.shape[1]))
    phys = jnp.take_along_axis(pt, idx, axis=2)                  # [B,Hkv,nsel]
    har = jnp.arange(hkv)[None, :, None]
    kg = k_pages[layer, phys, har]                         # [B,Hkv,nsel,ps,Dh]
    vg = v_pages[layer, phys, har]
    if k_scales is not None:
        kg = kg.astype(jnp.float32) * k_scales[layer, phys, har][..., None]
    if v_scales is not None:
        vg = vg.astype(jnp.float32) * v_scales[layer, phys, har][..., None]
    kg = kg.reshape(b, hkv, nsel * ps, dh)                 # [B,Hkv,n*ps,Dh]
    vg = vg.reshape(b, hkv, nsel * ps, dh)

    # token positions are LOGICAL (masking against kv_len)
    pos = idx[..., None] * ps + jnp.arange(ps)                   # [B,Hkv,nsel,ps]
    sc = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                    kg.astype(jnp.float32),
                    precision=qk_precision(q.dtype, k_pages.dtype,
                                           k_scales is not None)) * scale
    valid = (block_indices[..., None] >= 0) & (pos < kv_len[:, None, None, None])
    valid = valid.reshape(b, hkv, 1, nsel * ps)
    sc = jnp.where(valid, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(jnp.any(valid, axis=-1, keepdims=True), p, 0.0)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, vg.astype(jnp.float32),
                   precision=F32)
    return o.astype(q.dtype)


def paged_sparse_decode_splitk_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray, layer: jnp.ndarray,
                                   block_indices: jnp.ndarray,
                                   page_table: jnp.ndarray,
                                   kv_len: jnp.ndarray, *, block_size: int,
                                   num_splits: int,
                                   k_scales: Optional[jnp.ndarray] = None,
                                   v_scales: Optional[jnp.ndarray] = None
                                   ) -> jnp.ndarray:
    """Split-K twin of ``paged_sparse_decode_ref`` (semantic spec of the
    Pallas split-K kernel): the selected-block list is split into
    ``num_splits`` segments, each reduced to an unnormalized flash partial
    (acc_s, m_s, l_s), and the partials merge with the two-pass rescale

        m = max_s m_s,  l = sum_s l_s e^{m_s - m},
        o = sum_s acc_s e^{m_s - m} / l.

    ``num_splits=1`` delegates to the plain reference (bitwise identical)
    so the sharded paged engine can run split-free without changing code
    path. Selection order inside each split is preserved — only the
    cross-split reduction is restructured, which is exactly what the
    paper's num_split kernel does on-chip.
    """
    if num_splits <= 1:
        return paged_sparse_decode_ref(q, k_pages, v_pages, layer,
                                       block_indices, page_table, kv_len,
                                       block_size=block_size,
                                       k_scales=k_scales, v_scales=v_scales)
    b, hkv, g, dh = q.shape
    ps = k_pages.shape[3]
    assert ps == block_size, (ps, block_size)
    nsel = block_indices.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    per = -(-nsel // num_splits)
    pad = per * num_splits - nsel
    bi = block_indices
    if pad:
        bi = jnp.concatenate(
            [bi, jnp.full((b, hkv, pad), -1, bi.dtype)], axis=-1)
    bi = bi.reshape(b, hkv, num_splits, per)
    idx = jnp.maximum(bi, 0)

    npt = page_table.shape[1]
    pt = jnp.broadcast_to(page_table[:, None, None, :],
                          (b, hkv, num_splits, npt))
    phys = jnp.take_along_axis(pt, idx, axis=3)          # [B,Hkv,NS,per]
    har = jnp.arange(hkv)[None, :, None, None]
    kg = k_pages[layer, phys, har]                 # [B,Hkv,NS,per,ps,Dh]
    vg = v_pages[layer, phys, har]
    if k_scales is not None:
        kg = kg.astype(jnp.float32) * k_scales[layer, phys, har][..., None]
    if v_scales is not None:
        vg = vg.astype(jnp.float32) * v_scales[layer, phys, har][..., None]
    kg = kg.reshape(b, hkv, num_splits, per * ps, dh)
    vg = vg.reshape(b, hkv, num_splits, per * ps, dh)

    pos = idx[..., None] * ps + jnp.arange(ps)           # [B,Hkv,NS,per,ps]
    valid = (bi[..., None] >= 0) \
        & (pos < kv_len[:, None, None, None, None])
    valid = valid.reshape(b, hkv, num_splits, 1, per * ps)
    sc = jnp.einsum("bhgd,bhskd->bhsgk", q.astype(jnp.float32),
                    kg.astype(jnp.float32),
                    precision=qk_precision(q.dtype, k_pages.dtype,
                                           k_scales is not None)) * scale
    sc = jnp.where(valid, sc, NEG_INF)

    m_s = jnp.max(sc, axis=-1, keepdims=True)            # [B,Hkv,NS,G,1]
    p = jnp.where(sc > NEG_INF / 2, jnp.exp(sc - m_s), 0.0)
    l_s = jnp.sum(p, axis=-1, keepdims=True)
    acc_s = jnp.einsum("bhsgk,bhskd->bhsgd", p, vg.astype(jnp.float32),
                       precision=F32)

    m = jnp.max(m_s, axis=2, keepdims=True)              # over splits
    rescale = jnp.where(l_s > 0, jnp.exp(m_s - m), 0.0)
    l = jnp.sum(l_s * rescale, axis=2)                   # [B,Hkv,G,1]
    o = jnp.sum(acc_s * rescale, axis=2) / jnp.maximum(l, 1e-30)
    return o.astype(q.dtype)


def dense_decode_ref(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     kv_len: jnp.ndarray) -> jnp.ndarray:
    """Dense counterpart with the same head-major layout (baseline).
    q [B,Hkv,G,Dh]; caches [B,Hkv,S,Dh]."""
    b, hkv, g, dh = q.shape
    s = k_cache.shape[2]
    sc = jnp.einsum("bhgd,bhkd->bhgk", q.astype(jnp.float32),
                    k_cache.astype(jnp.float32),
                    precision=qk_precision(q.dtype, k_cache.dtype,
                                           False)) / math.sqrt(dh)
    valid = (jnp.arange(s)[None, :] < kv_len[:, None])[:, None, None, :]
    sc = jnp.where(valid, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32),
                   precision=F32)
    return o.astype(q.dtype)


def gate_gt_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                          gt_block_size: int,
                          segment_ids: Optional[jnp.ndarray] = None,
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Naive full-map causal attention that also returns block row-max logits.

    Used only at test scale (materialises [B, H, Lq, Lk]).
    segment_ids: [B, L] packing document ids; attention never crosses docs.
    """
    b, lq, h, dh = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    nb = lk // gt_block_size
    kf = jnp.repeat(k, g, axis=2) if g > 1 else k
    vf = jnp.repeat(v, g, axis=2) if g > 1 else v
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) / math.sqrt(dh)
    mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
    if segment_ids is not None:
        mask = mask[None] & (segment_ids[:, :, None] == segment_ids[:, None, :])
        s = jnp.where(mask[:, None], s, NEG_INF)
    else:
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, vf.astype(jnp.float32)).astype(q.dtype)
    blockmax = jnp.max(s.reshape(b, h, lq, nb, gt_block_size), axis=-1)
    return o, blockmax
