"""Jit'd dispatching wrappers over the Pallas kernels and their jnp oracles.

Models call these with ``impl=`` from ``DecodeOptions.impl``: the
platform's kernels by default (the compiled Mosaic kernels on a TPU, the
jnp path elsewhere), or a path the caller names — "ref", "pallas" or
"pallas_interpret" (the kernels in interpret mode, a CPU check).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.block_sparse_decode import (
    block_sparse_decode as _bsd_pallas,
    block_sparse_decode_paged as _bsd_paged_pallas,
    block_sparse_decode_paged_splitk as _bsd_splitk_pallas)
from repro.kernels.gate_gt_fwd import gate_gt_flash_fwd as _gt_pallas
from repro.kernels.gate_select import (fused_gate_select as _gs_pallas,
                                       fused_gate_select_paged as _gsp_pallas,
                                       gate_select_paged_ref as _gsp_ref,
                                       gate_select_ref as _gs_ref)


def sparse_decode(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                  block_indices: jnp.ndarray, kv_len: jnp.ndarray, *,
                  block_size: int, impl: str = "ref",
                  k_scales: Optional[jnp.ndarray] = None,
                  v_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """impl: 'ref' (jnp), 'pallas' (TPU), 'pallas_interpret' (CPU check).
    Caches are HEAD-MAJOR [B, Hkv, S, Dh] — consumed natively, no
    transpose on the decode path. ``k_scales``/``v_scales`` [B, Hkv, nb]:
    fused per-block dequant for int8 caches (None = fp path verbatim)."""
    if impl == "ref":
        return _ref.sparse_decode_ref(q, k_cache, v_cache, block_indices,
                                      kv_len, block_size=block_size,
                                      k_scales=k_scales, v_scales=v_scales)
    if impl == "pallas":
        return _bsd_pallas(q, k_cache, v_cache, block_indices, kv_len,
                           block_size=block_size,
                           k_scales=k_scales, v_scales=v_scales)
    if impl == "pallas_interpret":
        return _bsd_pallas(q, k_cache, v_cache, block_indices, kv_len,
                           block_size=block_size, interpret=True,
                           k_scales=k_scales, v_scales=v_scales)
    raise ValueError(impl)


def gate_select(qg: jnp.ndarray, kg: jnp.ndarray, n_valid: jnp.ndarray,
                cfg, max_selected: Optional[int] = None, *,
                impl: str = "ref") -> jnp.ndarray:
    """Fused gate scoring + discrete block selection for ONE decode step.

    qg [B,Hkv,Dg] post-rope gate queries; kg [B,Hkv,nb,Dg] HEAD-MAJOR
    K-compression cache (contiguous or paged per-slot gather); n_valid [B]
    visible blocks. Returns logical block ids [B,Hkv,k] int32 with -1
    padding — identical across impls (the kernel reproduces
    ``sparsity.select_blocks`` exactly, including top-k tie-breaking)."""
    if impl == "ref":
        return _gs_ref(qg, kg, n_valid, cfg, max_selected)
    if impl == "pallas":
        return _gs_pallas(qg, kg, n_valid, cfg, max_selected)
    if impl == "pallas_interpret":
        return _gs_pallas(qg, kg, n_valid, cfg, max_selected, interpret=True)
    raise ValueError(impl)


def gate_select_paged(qg: jnp.ndarray, kg_pages: jnp.ndarray,
                      page_table: jnp.ndarray, n_valid: jnp.ndarray,
                      cfg, max_selected: Optional[int] = None, *,
                      impl: str = "ref") -> jnp.ndarray:
    """Paged twin of ``gate_select``: scores one layer's Kg page pool
    [P,Hkv,Dg] straight through ``page_table`` [S,npt] — the Pallas paths
    never materialise the per-slot Kg gather (``fused_gate_select_paged``
    streams table-indexed pool rows); the jnp ref gathers first (the
    semantic spec). Returns logical block ids [S,Hkv,k], -1 padding."""
    if impl == "ref":
        return _gsp_ref(qg, kg_pages, page_table, n_valid, cfg, max_selected)
    if impl == "pallas":
        return _gsp_pallas(qg, kg_pages, page_table, n_valid, cfg,
                           max_selected)
    if impl == "pallas_interpret":
        return _gsp_pallas(qg, kg_pages, page_table, n_valid, cfg,
                           max_selected, interpret=True)
    raise ValueError(impl)


def paged_sparse_decode(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, layer: jnp.ndarray,
                        block_indices: jnp.ndarray,
                        page_table: jnp.ndarray, kv_len: jnp.ndarray, *,
                        block_size: int, impl: str = "ref",
                        k_scales: Optional[jnp.ndarray] = None,
                        v_scales: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Paged-KV twin of ``sparse_decode``: block_indices are LOGICAL block
    ids, translated through ``page_table`` [B, npt]. Pools are the
    layer-stacked HEAD-MAJOR [L, P, Hkv, page_size, Dh] (page_size ==
    block_size), read at the int32 ``layer``. ``k_scales``/``v_scales``
    [L, P, Hkv, 1] pool scale rows: fused dequant for int8 pools (None =
    fp path verbatim)."""
    if impl == "ref":
        return _ref.paged_sparse_decode_ref(
            q, k_pages, v_pages, layer, block_indices, page_table, kv_len,
            block_size=block_size, k_scales=k_scales, v_scales=v_scales)
    if impl == "pallas":
        return _bsd_paged_pallas(q, k_pages, v_pages, layer, block_indices,
                                 page_table, kv_len, block_size=block_size,
                                 k_scales=k_scales, v_scales=v_scales)
    if impl == "pallas_interpret":
        return _bsd_paged_pallas(q, k_pages, v_pages, layer, block_indices,
                                 page_table, kv_len, block_size=block_size,
                                 interpret=True,
                                 k_scales=k_scales, v_scales=v_scales)
    raise ValueError(impl)


def paged_sparse_decode_splitk(q: jnp.ndarray, k_pages: jnp.ndarray,
                               v_pages: jnp.ndarray, layer: jnp.ndarray,
                               block_indices: jnp.ndarray,
                               page_table: jnp.ndarray,
                               kv_len: jnp.ndarray, *, block_size: int,
                               num_splits: int,
                               impl: str = "ref",
                               k_scales: Optional[jnp.ndarray] = None,
                               v_scales: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
    """Split-K twin of ``paged_sparse_decode``: the selected list is
    reduced in ``num_splits`` independent flash partials that merge with a
    two-pass rescale (``num_splits=1`` is exactly the plain path). Used by
    the paged x sharded serving composition; see
    ``block_sparse_decode.block_sparse_decode_paged_splitk``.
    ``k_scales``/``v_scales``: fused int8 dequant, as ``paged_sparse_decode``."""
    if impl == "ref":
        return _ref.paged_sparse_decode_splitk_ref(
            q, k_pages, v_pages, layer, block_indices, page_table, kv_len,
            block_size=block_size, num_splits=num_splits,
            k_scales=k_scales, v_scales=v_scales)
    if impl == "pallas":
        return _bsd_splitk_pallas(q, k_pages, v_pages, layer, block_indices,
                                  page_table, kv_len, block_size=block_size,
                                  num_splits=num_splits,
                                  k_scales=k_scales, v_scales=v_scales)
    if impl == "pallas_interpret":
        return _bsd_splitk_pallas(q, k_pages, v_pages, layer, block_indices,
                                  page_table, kv_len, block_size=block_size,
                                  num_splits=num_splits, interpret=True,
                                  k_scales=k_scales, v_scales=v_scales)
    raise ValueError(impl)


def gate_gt_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      block_size: int, q_chunk: int = 256,
                      impl: str = "ref",
                      segment_ids: Optional[jnp.ndarray] = None,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Attention fwd + distillation blockmax. The 'chunked' impl is the
    memory-bounded jnp path used inside models (scan over q chunks)."""
    if impl == "ref":
        return _ref.gate_gt_attention_ref(q, k, v, gt_block_size=block_size,
                                          segment_ids=segment_ids)
    if impl == "chunked":
        from repro.models.common import chunked_attention
        if segment_ids is not None:
            raise NotImplementedError("packing masks: use impl='ref' in tests")
        o, bm = chunked_attention(q, k, v, causal=True, q_chunk=q_chunk,
                                  gt_block_size=block_size)
        return o, bm
    if impl in ("pallas", "pallas_interpret"):
        if segment_ids is not None:
            raise NotImplementedError("varlen Pallas GT kernel: jnp path only")
        return _gt_pallas(q, k, v, block_size=block_size, q_chunk=q_chunk,
                          interpret=(impl == "pallas_interpret"))
    raise ValueError(impl)
