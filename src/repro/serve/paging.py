"""Paged KV cache for continuous-batching sparse decode.

Storage is a global pool of fixed-size pages shared by every sequence in
flight; a per-slot page table maps logical KV block ids to physical pages.
The page size EQUALS the gate block size — the core invariant of this
subsystem: one page == one gate block, so the K-compression cache pages
alongside the raw KV (``kg_pages`` has exactly one row per physical page)
and admission/eviction can never desync the two. The gate's top-k still
emits *logical* block ids; the logical->physical translation happens at
gather time (pure-JAX path) or inside the kernel's scalar-prefetch
index_map (repro.kernels.block_sparse_decode).

Layout (``L`` = self-attn layers, ``P`` = pool pages, ``ps`` = page size;
HEAD-MAJOR — ISSUE 2 invariant: decode consumes the pools natively, no
page-pool-sized transpose anywhere on the hot path):
  k_pages / v_pages  [L, P, Hkv, ps, Dh]   post-rope keys / values
  kg_pages           [L, P, Hkv, Dg]       gate K-compression twin
  kmin/kmax_pages    [L, P, Hkv, Dh] f32   selection-metadata twin (Quest)
  k/v_scale_pages    [L, P, Hkv, 1]  f32   per-page per-head dequant scales
                                           (int8 pools only, ISSUE 9)
  page_table         [n_slots, npt] int32  physical ids; NULL_PAGE = empty
  cur_len / active   [n_slots]             per-slot ragged lengths

Quantized pools (``init_pages(..., quantize="int8")``): K/V pages hold
symmetric int8 (value = int8 * scale, scale = abs-max/127 per page per KV
head) and the scale rows ride the metacache pattern — one f32 row per
physical page, zeroed on lazy growth, rewritten on every append to the
trailing page and frozen once the page completes. Dequant happens inside
the block gather/loop of the decode kernels (fused — no fp copy of any
cache-sized array ever materializes); swap/evict move the int8 bytes plus
the scale rows, so host/disk budgets shrink ~4x. ``quantize=None``
keeps the fp pools and takes the original code path verbatim (the
``tests/golden_policy.npz`` bitwise contract).

Physical page 0 is reserved as the null/trash page: unallocated table
entries point at it and writes for inactive slots are routed there, so the
jitted decode step needs no host-side masking. The allocator never hands
out page 0.

Staleness contract (mirrors core.kcache): a page's ``kg_pages`` row is
only valid once the page is FULL. Partially-filled trailing pages keep a
zeroed row (freshly-admitted pages are zeroed explicitly — a recycled
page still holds the previous tenant's entry) and the serving engine
force-selects the trailing block, exactly like the contiguous engine.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.config import GateConfig, ModelConfig, Rope
from repro.core.kcache import finalize_block_kg

NULL_PAGE = 0


class PagedPages(NamedTuple):
    """Device-side page pools, stacked over self-attention layers.

    ``kmin_pages``/``kmax_pages`` are the paged twin of the selection-
    metadata cache (core.metacache): ONE min/max row per physical page
    (page == gate block), float32 for bitwise parity with the recompute
    reference. Allocated only for metadata-reading policies (QuestPolicy)
    and swept/swapped alongside ``kg_pages``.

    ``k_scale_pages``/``v_scale_pages`` (ISSUE 9) are the dequant scales of
    int8 K/V pools: one f32 row per physical page per KV head (value =
    int8 * scale). None for fp pools. Rank-4 on purpose — the existing
    ``distributed.sharding.paged_pool_pspecs`` ndim rule shards them over
    KV heads alongside the pools they describe."""
    k_pages: jnp.ndarray                 # [L, P, Hkv, ps, Dh]  (head-major)
    v_pages: jnp.ndarray                 # [L, P, Hkv, ps, Dh]
    kg_pages: Optional[jnp.ndarray]      # [L, P, Hkv, Dg]
    kmin_pages: Optional[jnp.ndarray] = None   # [L, P, Hkv, Dh] float32
    kmax_pages: Optional[jnp.ndarray] = None   # [L, P, Hkv, Dh] float32
    k_scale_pages: Optional[jnp.ndarray] = None   # [L, P, Hkv, 1] float32
    v_scale_pages: Optional[jnp.ndarray] = None   # [L, P, Hkv, 1] float32


INT8_MAX = 127.0


def quantize_block(x: jnp.ndarray, valid: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-(page, head) int8 quantization of fp page contents.

    x [..., ps, Dh] fp; valid bool broadcastable against x, masking the
    rows that hold real tokens (recycled pages carry the previous tenant's
    garbage — it must not inflate the scale). Returns (int8 page, f32
    scale [..., 1] over the last two axes collapsed): scale = abs-max/127
    over the valid region, 1.0 for an all-zero/empty region so dequant is
    exact there.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.where(valid, jnp.abs(xf), 0.0), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / INT8_MAX, 1.0)[..., None]
    q = jnp.clip(jnp.round(xf / scale[..., None]),
                 -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


def dequantize_block(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """int8 page [..., ps, Dh] x scale [..., 1] -> f32 page."""
    return q.astype(jnp.float32) * scale[..., None]


def init_pages(cfg: ModelConfig, num_pages: int, n_layers: int,
               dtype=None, with_meta: bool = False,
               ghost_rows: int = 0,
               quantize: Optional[str] = None) -> PagedPages:
    """Allocate the pools. ``ghost_rows`` (RaaS eviction, ISSUE 7) extends
    ONLY the gate/metadata pools (kg/kmin/kmax) by extra rows with ids in
    ``[num_pages, num_pages + ghost_rows)``: an evicted page's K/V leaves
    the device but its selection-side rows are parked in a ghost row and
    the page table repointed there, so selection math reads evicted
    blocks' scores/metadata through the table UNCHANGED — bitwise
    identical to the unevicted run — while the K/V rows are reclaimed.
    K/V pools never grow: attention consumers clamp ghost ids to the pool
    (optimistic execution; a selected-evicted block is detected via the
    touched-pages telemetry and replayed after restore).

    ``quantize="int8"`` (ISSUE 9) allocates int8 K/V pools plus the f32
    scale-row pools ([L, P, Hkv, 1], no ghost rows — an evicted page's
    scale rides its host ``PageEntry``, not a ghost row). The gate /
    metadata pools stay f32: they are ~ps*Dh/Dg smaller than K/V and
    keeping them full-precision keeps block SELECTION independent of the
    attention-value quantization."""
    dt = dtype or jnp.dtype(cfg.dtype)
    ps = cfg.gate.block_size
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    gate_rows = num_pages + ghost_rows
    kg = (jnp.zeros((n_layers, gate_rows, hkv, cfg.gate.d_gate), dt)
          if cfg.gate.enabled else None)
    def meta():
        # two DISTINCT buffers: the pools are donated through the jitted
        # step, and XLA rejects donating one buffer twice
        return (jnp.zeros((n_layers, gate_rows, hkv, dh), jnp.float32)
                if with_meta else None)
    if quantize is not None:
        if quantize != "int8":
            raise ValueError(f"quantize must be None or 'int8': {quantize!r}")
        kv_dt = jnp.int8
        def scale():
            # distinct buffers: same donation rule as meta() above
            return jnp.zeros((n_layers, num_pages, hkv, 1), jnp.float32)
        k_scale, v_scale = scale(), scale()
    else:
        kv_dt, k_scale, v_scale = dt, None, None
    return PagedPages(
        k_pages=jnp.zeros((n_layers, num_pages, hkv, ps, dh), kv_dt),
        v_pages=jnp.zeros((n_layers, num_pages, hkv, ps, dh), kv_dt),
        kg_pages=kg, kmin_pages=meta(), kmax_pages=meta(),
        k_scale_pages=k_scale, v_scale_pages=v_scale)


@functools.partial(jax.jit, static_argnames=("block_size",),
                   donate_argnums=(0,))
def scatter_prefill(pages: PagedPages, k_cache: jnp.ndarray,
                    v_cache: jnp.ndarray, kg_cache: Optional[jnp.ndarray],
                    length: jnp.ndarray, page_ids: jnp.ndarray,
                    block_size: int,
                    kmin_cache: Optional[jnp.ndarray] = None,
                    kmax_cache: Optional[jnp.ndarray] = None) -> PagedPages:
    """Copy one request's contiguous prefill caches into its pages.

    k_cache/v_cache: HEAD-MAJOR [L, 1, Hkv, S_max, Dh] from ``lm_prefill``
    with S_max a whole number of pages; ``page_ids`` covers the request's
    pages (prompt pages, plus the full reservation under upfront
    admission), PADDED to a power-of-two with NULL_PAGE
    (``pad_page_ids``) so — together with ``length`` being a TRACED array
    (not a static) — the jit cache holds one program per (cache bucket,
    id bucket) pair, not one per distinct prompt length (ISSUE 5
    bucketing). Every cache page is copied; ids beyond the prompt are
    either NULL (trash page) or reserved growth pages whose K/V reads are
    masked by ``kv_len`` anyway. kg rows beyond the ``length //
    block_size`` complete blocks are zeroed — recycled pages may hold the
    previous tenant's entries — and the selection-metadata rows
    (``kmin_cache``/``kmax_cache`` [L, 1, Hkv, nb, Dh] from a
    metacache-building prefill) follow the exact same rule. (This scatter
    is prefill-time, so the page-major regrouping here is the allowed
    one-time conversion.)
    """
    n_ids = page_ids.shape[0]
    nl, _, hkv, s_max, dh = k_cache.shape
    n_cache = s_max // block_size
    src = jnp.minimum(jnp.arange(n_ids), n_cache - 1)   # clamped row gather

    def page_rows(cache):                # [L,1,Hkv,S,Dh] -> [L,n_ids,...]
        rows = jnp.swapaxes(
            cache[:, 0].reshape(nl, hkv, n_cache, block_size, dh), 1, 2)
        return rows[:, src]

    if pages.k_scale_pages is not None:
        # int8 pools (ISSUE 9): quantize each scattered page per (page,
        # head) over its VALID token rows only — ids beyond the prompt get
        # clamp-gathered garbage whose abs-max must not pollute the scale.
        tok = (jnp.arange(n_ids)[:, None] * block_size
               + jnp.arange(block_size)[None, :])          # [n_ids, ps]
        valid = (tok < length)[None, :, None, :, None]     # -> page axes
        kq, k_sc = quantize_block(page_rows(k_cache), valid)
        vq, v_sc = quantize_block(page_rows(v_cache), valid)
        k_pages = pages.k_pages.at[:, page_ids].set(kq)
        v_pages = pages.v_pages.at[:, page_ids].set(vq)
        k_scale_pages = pages.k_scale_pages.at[:, page_ids].set(k_sc)
        v_scale_pages = pages.v_scale_pages.at[:, page_ids].set(v_sc)
    else:
        k_pages = pages.k_pages.at[:, page_ids].set(
            page_rows(k_cache).astype(pages.k_pages.dtype))
        v_pages = pages.v_pages.at[:, page_ids].set(
            page_rows(v_cache).astype(pages.v_pages.dtype))
        k_scale_pages = v_scale_pages = None
    nbc = length // block_size           # traced: complete prompt blocks

    def row_scatter(pool, rows_cache):
        """Zero every listed page's row, then the ``nbc`` complete-block
        rows from the contiguous cache (head-major [L,1,Hkv,nb,*])."""
        new = jnp.zeros((nl, n_ids) + pool.shape[2:], pool.dtype)
        if rows_cache is not None:
            nb = rows_cache.shape[3]
            srcr = jnp.minimum(jnp.arange(n_ids), nb - 1)
            rows = jnp.swapaxes(rows_cache[:, 0], 1, 2)[:, srcr]
            keep = (jnp.arange(n_ids) < nbc).reshape(
                (1, n_ids) + (1,) * (pool.ndim - 2))
            new = jnp.where(keep, rows.astype(pool.dtype), new)
        return pool.at[:, page_ids].set(new)

    kg_pages = pages.kg_pages
    if kg_pages is not None:
        kg_pages = row_scatter(kg_pages, kg_cache)
    kmin_pages, kmax_pages = pages.kmin_pages, pages.kmax_pages
    if kmin_pages is not None:
        kmin_pages = row_scatter(kmin_pages, kmin_cache)
        kmax_pages = row_scatter(kmax_pages, kmax_cache)
    return PagedPages(k_pages, v_pages, kg_pages, kmin_pages, kmax_pages,
                      k_scale_pages, v_scale_pages)


def append_token_paged(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                       kg_pages: Optional[jnp.ndarray], layer: jnp.ndarray,
                       kr_new: jnp.ndarray, v_new: jnp.ndarray,
                       page_table: jnp.ndarray, cur_len: jnp.ndarray,
                       active: jnp.ndarray, gate_params: Optional[Dict],
                       cfg: GateConfig, *, rope: Rope
                       ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                  Optional[jnp.ndarray]]:
    """ONE layer's paged twin of the contiguous write + ``update_kcache``.

    The pools are the layer-STACKED ones ([L, P, ...]) and ``layer`` the
    int32 layer index: the write lands in place in the stacked buffer, so
    the decode layer loop can carry the pools whole (no per-layer slice or
    restack of a pool-sized array). kr_new/v_new: [S, Hkv, Dh] the new
    token's post-rope K / V per slot. Writes land at (layer,
    page_table[slot, cur_len // ps], :, cur_len % ps); rows with
    ``active == False`` are routed to the null page. When a slot's page
    completes ((cur_len+1) % ps == 0) the page's keys are rotated back to
    the pre-rope frame with ``rope``, the model's RoPE (same trick as
    kcache.update_kcache), and pooled+projected into that page's
    ``kg_pages`` row.
    """
    ps = cfg.block_size
    n_slots = cur_len.shape[0]
    sidx = jnp.arange(n_slots)
    logical = cur_len // ps
    off = cur_len % ps
    phys = page_table[sidx, logical]                       # [S]
    phys = jnp.where(active, phys, NULL_PAGE)
    # every index spelled out over the leading four axes: a slice between
    # the indexed axes would make XLA scatter into a transposed copy of
    # the whole stacked pool
    at = (layer, phys[:, None], jnp.arange(k_pages.shape[2])[None, :],
          off[:, None])                                    # -> [S, Hkv]
    k_pages = k_pages.at[at].set(kr_new.astype(k_pages.dtype))
    v_pages = v_pages.at[at].set(v_new.astype(v_pages.dtype))

    if kg_pages is None or gate_params is None:
        return k_pages, v_pages, kg_pages

    kg_pages = finalize_kg_paged(k_pages, kg_pages, layer, page_table,
                                 cur_len, active, gate_params, cfg,
                                 rope=rope)
    return k_pages, v_pages, kg_pages


def append_token_paged_quant(k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                             kg_pages: Optional[jnp.ndarray],
                             k_scale: jnp.ndarray, v_scale: jnp.ndarray,
                             layer: jnp.ndarray,
                             kr_new: jnp.ndarray, v_new: jnp.ndarray,
                             page_table: jnp.ndarray, cur_len: jnp.ndarray,
                             active: jnp.ndarray,
                             gate_params: Optional[Dict],
                             cfg: GateConfig, *, rope: Rope
                             ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                        Optional[jnp.ndarray],
                                        jnp.ndarray, jnp.ndarray]:
    """Int8 twin of ``append_token_paged``, on the same stacked
    pools and ``layer`` index.

    The trailing partial page is REQUANTIZED per append: dequant it with
    its stored scale row, insert the new fp token row, recompute the
    abs-max scale over the now-valid rows, and write the whole int8 page
    plus its scale row back. One physical page per slot is read and
    written — O(page_size), the same cost class as the Kg finalize, and
    the only page whose bytes ever change; completed pages' int8 contents
    are frozen. Inactive slots route to the null/trash page like the fp
    path. Returns (k_pages, v_pages, kg_pages, k_scale, v_scale); the Kg
    row of a just-completed page is finalized from the DEQUANTIZED keys
    (selection consumes what attention will actually read).
    """
    ps = cfg.block_size
    n_slots = cur_len.shape[0]
    sidx = jnp.arange(n_slots)
    logical = cur_len // ps
    off = cur_len % ps
    phys = page_table[sidx, logical]                       # [S]
    phys = jnp.where(active, phys, NULL_PAGE)
    onehot = jnp.arange(ps)[None, :] == off[:, None]       # [S, ps]
    valid = (jnp.arange(ps)[None, :] <= off[:, None]
             )[:, None, :, None]                           # [S,1,ps,1]

    def requant(pages_q, scale_pool, new_row):
        page = dequantize_block(pages_q[layer, phys],
                                scale_pool[layer, phys])
        page = jnp.where(onehot[:, None, :, None],
                         new_row.astype(jnp.float32)[:, :, None, :], page)
        q, sc = quantize_block(page, valid)
        return (pages_q.at[layer, phys].set(q),
                scale_pool.at[layer, phys].set(sc))

    k_pages, k_scale = requant(k_pages, k_scale, kr_new)
    v_pages, v_scale = requant(v_pages, v_scale, v_new)

    if kg_pages is None or gate_params is None:
        return k_pages, v_pages, kg_pages, k_scale, v_scale

    kg_pages = finalize_kg_paged(k_pages, kg_pages, layer, page_table,
                                 cur_len, active, gate_params, cfg,
                                 rope=rope, k_scale=k_scale)
    return k_pages, v_pages, kg_pages, k_scale, v_scale


def finalize_kg_paged(k_pages: jnp.ndarray, kg_pages: jnp.ndarray,
                      layer: jnp.ndarray,
                      page_table: jnp.ndarray, cur_len: jnp.ndarray,
                      active: jnp.ndarray, gate_params: Dict,
                      cfg: GateConfig, *, rope: Rope,
                      k_scale: Optional[jnp.ndarray] = None
                      ) -> jnp.ndarray:
    """Finalize the Kg row of each slot's just-completed page, at
    ``[layer, phys]`` of the stacked pools.

    Called AFTER the new token's key is written: when a slot's page
    completes ((cur_len+1) % ps == 0) the page's keys are rotated back to
    the pre-rope frame with ``rope``, the model's RoPE (same trick as
    kcache.update_kcache), and pooled+projected into that page's
    ``kg_pages`` row. Inactive /
    incomplete slots route the write to the null page. Split out from
    ``append_token_paged`` so a SelectionSchedule can gate the Kg advance
    (selecting layers only) independently of the K/V append, which always
    happens. ``k_scale`` (int8 pools) dequantizes the gathered page before
    pooling — O(page_size), not cache-sized.
    """
    ps = cfg.block_size
    sidx = jnp.arange(cur_len.shape[0])
    logical = cur_len // ps
    phys = page_table[sidx, logical]                       # [S]
    phys = jnp.where(active, phys, NULL_PAGE)
    completed = active & (((cur_len + 1) % ps) == 0)       # [S]

    def one_slot(page_k, lg):
        # page_k [Hkv, ps, Dh] post-rope keys of the (now full) page;
        # flip the tiny page corner to the seq-major frame finalize expects
        return finalize_block_kg(gate_params, jnp.swapaxes(page_k, 0, 1),
                                 lg * ps, lg, cfg,
                                 is_roped=True, rope=rope)

    blk = k_pages[layer, phys]                             # [S, Hkv, ps, Dh]
    if k_scale is not None:
        blk = dequantize_block(blk, k_scale[layer, phys])
    kg_new = jax.vmap(one_slot)(blk, logical)              # [S, Hkv, Dg]
    phys_kg = jnp.where(completed, phys, NULL_PAGE)
    kg_cur = kg_pages[layer, phys_kg]
    kg_write = jnp.where(completed[:, None, None],
                         kg_new.astype(kg_pages.dtype), kg_cur)
    return kg_pages.at[layer, phys_kg].set(kg_write)


def append_meta_paged(kmin_pages: jnp.ndarray, kmax_pages: jnp.ndarray,
                      k_pages: jnp.ndarray, layer: jnp.ndarray,
                      page_table: jnp.ndarray,
                      cur_len: jnp.ndarray, active: jnp.ndarray,
                      page_size: int,
                      k_scale: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """ONE layer's paged twin of ``metacache.update_metacache``, written
    at ``[layer, phys]`` of the stacked pools.

    Called AFTER ``append_token_paged`` wrote the new token's key: when a
    slot's page completes ((cur_len+1) % ps == 0) that page's key min/max
    is finalized into its ``kmin_pages``/``kmax_pages`` row — reading
    exactly one physical page per slot (O(page_size), the metadata analog
    of the Kg finalize). Inactive rows route to the null page. ``k_scale``
    (int8 pools) dequantizes the gathered page before the min/max.
    """
    ps = page_size
    n_slots = cur_len.shape[0]
    sidx = jnp.arange(n_slots)
    logical = cur_len // ps
    phys = page_table[sidx, logical]                       # [S]
    phys = jnp.where(active, phys, NULL_PAGE)
    completed = active & (((cur_len + 1) % ps) == 0)       # [S]

    from repro.core.metacache import _block_minmax
    blk = k_pages[layer, phys]                             # [S, Hkv, ps, Dh]
    if k_scale is not None:
        blk = dequantize_block(blk, k_scale[layer, phys])
    mn_new, mx_new = _block_minmax(blk, jnp.ones((1, 1, ps, 1), bool))
    phys_w = jnp.where(completed, phys, NULL_PAGE)
    wm = completed[:, None, None]
    kmin_pages = kmin_pages.at[layer, phys_w].set(
        jnp.where(wm, mn_new, kmin_pages[layer, phys_w]))
    kmax_pages = kmax_pages.at[layer, phys_w].set(
        jnp.where(wm, mx_new, kmax_pages[layer, phys_w]))
    return kmin_pages, kmax_pages


def gather_kg(kg_pages: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """[P, Hkv, Dg] x [S, npt] -> per-slot HEAD-MAJOR logical Kg view
    [S, Hkv, npt, Dg] (feeds the fused gate-select kernel directly)."""
    return jnp.swapaxes(kg_pages[page_table], 1, 2)


def gather_kv(pages: jnp.ndarray, layer: jnp.ndarray,
              page_table: jnp.ndarray,
              scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Stacked [L, P, Hkv, ps, Dh] at ``layer`` x [S, npt] -> head-major
    contiguous view [S, Hkv, npt*ps, Dh].

    Dense-attention fallback path (and debugging) ONLY — this materialises
    a cache-sized copy by construction (dense reads the whole cache); the
    sparse hot path never calls it, it gathers selected pages only.
    ``scale`` [L, P, Hkv, 1] dequantizes int8 pools during the gather.
    """
    s, npt = page_table.shape
    g = pages[layer, page_table]             # [S, npt, Hkv, ps, Dh]
    if scale is not None:
        g = dequantize_block(g, scale[layer, page_table])
    g = jnp.swapaxes(g, 1, 2)                # [S, Hkv, npt, ps, Dh]
    return g.reshape(s, pages.shape[2], npt * pages.shape[3],
                     pages.shape[4])


class PageAllocator:
    """Host-side free-list allocator over the physical page pool.

    Page 0 (NULL_PAGE) is reserved. Allocation is LIFO over the free list
    so freshly-freed pages are reused first (cache-warm + makes free-list
    reuse observable in tests). ``min_free`` records the low-watermark of
    the free list over the allocator's lifetime (peak-occupancy telemetry
    for the serving stats).
    """

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self.min_free = len(self._free)

    @property
    def num_free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages, or None if the pool can't satisfy the request."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self.min_free = min(self.min_free, len(self._free))
        return out

    def free(self, ids: Sequence[int]) -> None:
        for i in ids:
            if i == NULL_PAGE:
                raise ValueError("page 0 is reserved")
            if i in self._free:
                raise ValueError(f"double free of page {i}")
            self._free.append(int(i))


# ---------------------------------------------------------------------------
# lazy allocation + preemption/swap device helpers (ISSUE 4)
# ---------------------------------------------------------------------------

def pad_page_ids(ids: Sequence[int], *, min_len: int = 1) -> jnp.ndarray:
    """Pad a host-side page-id list to the next power-of-two length with
    NULL_PAGE, so the jitted page helpers below compile O(log pool)
    distinct programs instead of one per distinct page count. Page 0 is
    the trash page: reading its rows is harmless and writes to it are
    discarded by design, so the padding ids are semantically inert."""
    n = max(len(ids), min_len)
    bucket = 1 << (n - 1).bit_length()
    return jnp.asarray(list(ids) + [NULL_PAGE] * (bucket - len(ids)),
                       jnp.int32)


def to_dev(a):
    """A host page row (swap/page entry field) on the device; None passes
    through."""
    return None if a is None else jnp.asarray(a)


@functools.partial(jax.jit, donate_argnums=(0,))
def reset_kg_rows(pages: PagedPages, page_ids: jnp.ndarray) -> PagedPages:
    """Zero the Kg AND selection-metadata rows of freshly (lazily)
    allocated pages.

    A recycled physical page still holds the previous tenant's Kg /
    min-max entries; under upfront reservation ``scatter_prefill`` zeroed
    every reserved page's rows at admission, so lazy growth must do the
    same at allocation time to keep the staleness contract (a partial
    trailing page reads a ZERO row, exactly like the contiguous cache).
    K/V page contents need no reset: every read is masked by the logical
    ``kv_len``.
    """
    out = pages
    if pages.kg_pages is not None:
        out = out._replace(kg_pages=out.kg_pages.at[:, page_ids].set(0.0))
    if pages.kmin_pages is not None:
        out = out._replace(
            kmin_pages=out.kmin_pages.at[:, page_ids].set(0.0),
            kmax_pages=out.kmax_pages.at[:, page_ids].set(0.0))
    if pages.k_scale_pages is not None:
        # zero scale -> a recycled page's stale int8 bytes dequantize to
        # exactly 0 until the first append/scatter rewrites the row
        out = out._replace(
            k_scale_pages=out.k_scale_pages.at[:, page_ids].set(0.0),
            v_scale_pages=out.v_scale_pages.at[:, page_ids].set(0.0))
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_gate_rows(pages: PagedPages, src_ids: jnp.ndarray,
                   dst_ids: jnp.ndarray) -> PagedPages:
    """Copy gate/metadata rows (kg/kmin/kmax) from ``src_ids`` to
    ``dst_ids`` — the evict-time park of a page's selection-side state
    into a ghost row (and nothing else: K/V rows are extracted to host by
    ``extract_pages`` and then simply reclaimed). Both id lists are padded
    with NULL_PAGE by the caller; the padding copies row 0 onto itself,
    which is inert."""
    out = pages
    if pages.kg_pages is not None:
        out = out._replace(kg_pages=out.kg_pages.at[:, dst_ids].set(
            out.kg_pages[:, src_ids]))
    if pages.kmin_pages is not None:
        out = out._replace(
            kmin_pages=out.kmin_pages.at[:, dst_ids].set(
                out.kmin_pages[:, src_ids]),
            kmax_pages=out.kmax_pages.at[:, dst_ids].set(
                out.kmax_pages[:, src_ids]))
    return out


@jax.jit
def extract_pages(pages: PagedPages, page_ids: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray],
                             Optional[jnp.ndarray], Optional[jnp.ndarray],
                             Optional[jnp.ndarray], Optional[jnp.ndarray]]:
    """Gather one request's pages for swap-out (preemption).

    page_ids [n] physical ids in LOGICAL order -> (k [L,n,Hkv,ps,Dh],
    v [L,n,Hkv,ps,Dh], kg [L,n,Hkv,Dg] | None, kmin [L,n,Hkv,Dh] | None,
    kmax | None, k_scale [L,n,Hkv,1] | None, v_scale | None). Int8 pools
    swap their RAW quantized bytes plus the scale rows — the round trip
    is bitwise on the stored representation and ~4x cheaper on the host/
    disk tiers. The caller device_gets the result into the host swap
    space (serve.offload.HostSwapSpace).
    """
    k = pages.k_pages[:, page_ids]
    v = pages.v_pages[:, page_ids]
    kg = pages.kg_pages[:, page_ids] if pages.kg_pages is not None else None
    kmin = (pages.kmin_pages[:, page_ids]
            if pages.kmin_pages is not None else None)
    kmax = (pages.kmax_pages[:, page_ids]
            if pages.kmax_pages is not None else None)
    k_scale = (pages.k_scale_pages[:, page_ids]
               if pages.k_scale_pages is not None else None)
    v_scale = (pages.v_scale_pages[:, page_ids]
               if pages.v_scale_pages is not None else None)
    return k, v, kg, kmin, kmax, k_scale, v_scale


@functools.partial(jax.jit, donate_argnums=(0,))
def restore_pages(pages: PagedPages, k: jnp.ndarray, v: jnp.ndarray,
                  kg: Optional[jnp.ndarray],
                  page_ids: jnp.ndarray,
                  kmin: Optional[jnp.ndarray] = None,
                  kmax: Optional[jnp.ndarray] = None,
                  k_scale: Optional[jnp.ndarray] = None,
                  v_scale: Optional[jnp.ndarray] = None) -> PagedPages:
    """Scatter swapped-out page contents into a fresh set of physical
    pages (re-admission after preemption). The new physical ids may differ
    from the original ones — decode math is placement-invariant (every
    access goes through the page table), so the round trip is bitwise
    lossless; the selection-metadata and quant-scale rows ride along the
    same way (int8 pools restore raw bytes + scales, no re-quantization)."""
    k_pages = pages.k_pages.at[:, page_ids].set(
        k.astype(pages.k_pages.dtype))
    v_pages = pages.v_pages.at[:, page_ids].set(
        v.astype(pages.v_pages.dtype))
    kg_pages = pages.kg_pages
    if kg_pages is not None and kg is not None:
        kg_pages = kg_pages.at[:, page_ids].set(kg.astype(kg_pages.dtype))
    kmin_pages, kmax_pages = pages.kmin_pages, pages.kmax_pages
    if kmin_pages is not None and kmin is not None:
        kmin_pages = kmin_pages.at[:, page_ids].set(
            kmin.astype(kmin_pages.dtype))
        kmax_pages = kmax_pages.at[:, page_ids].set(
            kmax.astype(kmax_pages.dtype))
    k_scale_pages, v_scale_pages = pages.k_scale_pages, pages.v_scale_pages
    if k_scale_pages is not None and k_scale is not None:
        k_scale_pages = k_scale_pages.at[:, page_ids].set(k_scale)
        v_scale_pages = v_scale_pages.at[:, page_ids].set(v_scale)
    return PagedPages(k_pages, v_pages, kg_pages, kmin_pages, kmax_pages,
                      k_scale_pages, v_scale_pages)
