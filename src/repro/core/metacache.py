"""Selection-metadata cache: incremental per-block key min/max (ISSUE 5).

The Quest-style policies rank blocks by a q.k upper bound from per-block
key min/max. Recomputing that metadata from the whole K cache every decode
step is an O(S) read — the exact cost class sparse attention exists to
avoid, and the reason the PR-3 `policies` sweep could not compare methods
at decode-realistic cost. This module is the metadata twin of the Kg
K-compression cache (core.kcache): prefill bulk-builds it, decode pays an
O(block_size) update only when ``cur_len`` crosses a block boundary, and
the trailing PARTIAL block is overlaid on the fly from its (tiny,
block-sized) slice of the K cache.

Layout (HEAD-MAJOR, the decode-path invariant):
  kmin / kmax   [B, Hkv, nb_max, Dh]  float32
  n_complete    [B] int32             finalized entries per row

float32 storage is deliberate: the recompute reference
(``core.quest.quest_meta_decode``) reduces in float32, and the binding
contract of this cache is BITWISE equality with that reference on every
visible block — a bf16 round trip would break it for <2/block_size of the
KV cache's footprint in savings.

Staleness contract (mirrors core.kcache exactly): entries at slots
``>= n_complete`` are stale; the trailing partial block is never read from
the cache — ``trailing_meta`` recomputes it each step from the last
``block_size`` keys (O(bs), not O(S)) and ``overlay_trailing`` splices it
into the view a policy scores. ``cur_len == 0`` rows (empty/retired decode
slots) never finalize anything — the same guard ``kcache.update_kcache``
applies (ISSUE 5 satellite).

The paged twin lives in ``serve.paging``: min/max PAGE POOLS
``[L, P, Hkv, Dh]`` with one row per physical page (page == gate block),
allocated/swept/swapped alongside ``kg_pages`` so Quest scores straight
off pages through the page table with no cache-sized gather.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class SelectionMetaCache(NamedTuple):
    kmin: jnp.ndarray           # [B, Hkv, nb_max, Dh] float32 (HEAD-MAJOR)
    kmax: jnp.ndarray           # [B, Hkv, nb_max, Dh] float32
    n_complete: jnp.ndarray     # [B] int32: finalized block entries


def init_metacache(batch: int, max_blocks: int, n_kv_heads: int,
                   head_dim: int) -> SelectionMetaCache:
    return SelectionMetaCache(
        kmin=jnp.zeros((batch, n_kv_heads, max_blocks, head_dim),
                       jnp.float32),
        kmax=jnp.zeros((batch, n_kv_heads, max_blocks, head_dim),
                       jnp.float32),
        n_complete=jnp.zeros((batch,), jnp.int32))


def _block_minmax(blk: jnp.ndarray, valid: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """min/max over one block's seq axis with out-of-range tokens masked —
    the SAME reduction (float32, inf-mask, finite-fix) as
    ``quest.quest_meta_decode`` so finalized entries are bitwise-equal to
    the recompute reference. blk [..., bs, Dh]; valid [..., bs, 1] bool."""
    kb = blk.astype(jnp.float32)
    kmin = jnp.min(jnp.where(valid, kb, jnp.inf), axis=-2)
    kmax = jnp.max(jnp.where(valid, kb, -jnp.inf), axis=-2)
    kmin = jnp.where(jnp.isfinite(kmin), kmin, 0.0)
    kmax = jnp.where(jnp.isfinite(kmax), kmax, 0.0)
    return kmin, kmax


def prefill_metacache(cache: SelectionMetaCache, k_cache: jnp.ndarray,
                      kv_len: jnp.ndarray, block_size: int
                      ) -> SelectionMetaCache:
    """Bulk-populate from a prefilled HEAD-MAJOR K cache [B, Hkv, S, Dh].

    All nb = S // block_size entries are written (tokens >= ``kv_len`` are
    masked out, so the trailing partial entry is exact *for this length*
    — it goes stale on the first decode step and is overlaid from then
    on); ``n_complete`` records only the full blocks. Prefill owns the one
    O(S) pass, decode never repeats it."""
    from repro.core.quest import quest_meta_decode
    kmin, kmax = quest_meta_decode(k_cache, kv_len, block_size)
    nb = kmin.shape[2]
    new_kmin = cache.kmin.at[:, :, :nb].set(kmin)
    new_kmax = cache.kmax.at[:, :, :nb].set(kmax)
    return SelectionMetaCache(new_kmin, new_kmax,
                              (kv_len // block_size).astype(jnp.int32))


def update_metacache(cache: SelectionMetaCache, k_cache: jnp.ndarray,
                     cur_len: jnp.ndarray, block_size: int
                     ) -> SelectionMetaCache:
    """Decode-time incremental update — O(block_size) per step.

    k_cache: [B, Hkv, S_max, Dh] head-major (post-rope) key cache;
    cur_len: [B] length *after* appending the newest token. When a row
    crosses a block boundary the just-completed block's min/max is
    finalized at slot ``cur_len // bs - 1`` (same trigger and ragged
    where-masking as ``kcache.update_kcache``); rows with ``cur_len == 0``
    (empty/retired slots) are never treated as completed."""
    bs = block_size
    completed = ((cur_len % bs) == 0) & (cur_len > 0)     # [B] bool
    blk_idx = jnp.maximum(cur_len // bs - 1, 0)           # [B]
    start = blk_idx * bs

    def one_row(k_raw, st):
        # k_raw [Hkv, S, Dh]: slice the completed block (every position
        # valid — the block is full by the boundary-crossing trigger)
        blk = jax.lax.dynamic_slice_in_dim(k_raw, st, bs, axis=1)
        return _block_minmax(blk, jnp.ones((1, bs, 1), bool))

    mn_new, mx_new = jax.vmap(one_row)(k_cache, start)        # [B,Hkv,Dh]
    cur_mn = jax.vmap(lambda c, i: c[:, i])(cache.kmin, blk_idx)
    cur_mx = jax.vmap(lambda c, i: c[:, i])(cache.kmax, blk_idx)
    wm = completed[:, None, None]
    mn_w = jnp.where(wm, mn_new, cur_mn)
    mx_w = jnp.where(wm, mx_new, cur_mx)
    new_kmin = jax.vmap(lambda c, i, v: c.at[:, i].set(v))(
        cache.kmin, blk_idx, mn_w)
    new_kmax = jax.vmap(lambda c, i, v: c.at[:, i].set(v))(
        cache.kmax, blk_idx, mx_w)
    new_n = jnp.where(completed, blk_idx + 1, cache.n_complete)
    return SelectionMetaCache(new_kmin, new_kmax, new_n.astype(jnp.int32))


def trailing_meta(k_cache: jnp.ndarray, cur_len: jnp.ndarray,
                  block_size: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray]:
    """On-the-fly min/max of the TRAILING (possibly partial) block.

    An O(block_size) dynamic slice per row — never an O(S) read. Returns
    (tmin [B, Hkv, Dh], tmax, t_idx [B] trailing block index). Bitwise
    equal to the recompute reference's entry for that block: same slice,
    same masked float32 reduction."""
    bs = block_size
    t_idx = jnp.maximum(-(-cur_len // bs) - 1, 0)          # [B]
    start = t_idx * bs
    rem = cur_len - start                                   # tokens in block

    def one_row(k_raw, st, r):
        blk = jax.lax.dynamic_slice_in_dim(k_raw, st, bs, axis=1)
        valid = (jnp.arange(bs) < r)[None, :, None]
        return _block_minmax(blk, valid)

    tmin, tmax = jax.vmap(one_row)(k_cache, start, rem)     # [B, Hkv, Dh]
    return tmin, tmax, t_idx


def trailing_meta_paged(k_pages: jnp.ndarray, layer: jnp.ndarray,
                        page_table: jnp.ndarray,
                        cur_len: jnp.ndarray, page_size: int,
                        k_scale: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Paged twin of ``trailing_meta``: one physical page per slot.

    k_pages [L, P, Hkv, ps, Dh] (stacked, read at ``layer``); page_table
    [S, npt]; cur_len [S]. Reads exactly ONE page per slot
    (O(page_size)); rows with ``cur_len == 0`` read the null page and
    collapse to zeros. ``k_scale`` [L, P, Hkv, 1] (int8 pools)
    dequantizes the gathered page first — the metadata describes the
    values attention will actually read."""
    ps = page_size
    sidx = jnp.arange(cur_len.shape[0])
    t_idx = jnp.maximum(-(-cur_len // ps) - 1, 0)           # [S] logical
    phys = page_table[sidx, t_idx]                          # [S]
    rem = cur_len - t_idx * ps
    blk = k_pages[layer, phys]                              # [S, Hkv, ps, Dh]
    if k_scale is not None:
        from repro.serve.paging import dequantize_block
        blk = dequantize_block(blk, k_scale[layer, phys])
    valid = (jnp.arange(ps)[None, :] < rem[:, None])[:, None, :, None]
    tmin, tmax = _block_minmax(blk, valid)
    return tmin, tmax, t_idx


class BlockHeat:
    """Host-side recency/mass twin of the selection metadata (ISSUE 7).

    RaaS-style (arXiv 2502.11147) retention signal for the page-eviction
    victim model: per (slot, logical block), the step of the LAST time any
    head selected the block (``last_touch``, the timestamp rows PR 5's
    substrate was built for) and an exponential moving average of its
    selection mass (``ema`` — how often the block keeps being re-touched).
    Updated once per COMMITTED decode step from the touched-pages
    telemetry the jitted step already emits; replayed (discarded) runs are
    never observed, so the signal matches what the request actually
    attended to. Plain numpy on purpose: the victim model runs on the
    host between steps, exactly like the scheduler."""

    def __init__(self, n_slots: int, n_blocks: int, decay: float = 0.8):
        self.decay = float(decay)
        self.step = 0
        self.last_touch = np.full((n_slots, n_blocks), -1, np.int64)
        self.ema = np.zeros((n_slots, n_blocks), np.float32)

    def observe(self, touched: np.ndarray, active: np.ndarray) -> None:
        """touched [n_slots, n_blocks] bool (any layer, any head selected
        the block this step); active [n_slots] bool."""
        self.step += 1
        t = touched & active[:, None]
        self.ema[active] *= self.decay
        self.ema[t] += 1.0
        self.last_touch[t] = self.step

    def reset_row(self, slot: int) -> None:
        """A slot changed tenants (admission/retire/preempt): heat from
        the previous request must not bias the new one's victim model."""
        self.last_touch[slot] = -1
        self.ema[slot] = 0.0


def overlay_trailing(kmin: jnp.ndarray, kmax: jnp.ndarray,
                     tmin: jnp.ndarray, tmax: jnp.ndarray,
                     t_idx: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Splice the per-step trailing min/max into the cached view.

    kmin/kmax [B, Hkv, nb, Dh] (cached, trailing entry stale); tmin/tmax
    [B, Hkv, Dh]; t_idx [B]. When the trailing block is COMPLETE the
    overlay equals the finalized cache entry (same reduction over the same
    keys), so overlaying unconditionally is bitwise-safe. The result is a
    metadata-sized temporary — never cache-sized."""
    nb = kmin.shape[2]
    at_t = (jnp.arange(nb)[None, None, :, None]
            == t_idx[:, None, None, None])                  # [B,1,nb,1]
    kmin = jnp.where(at_t, tmin[:, :, None, :], kmin)
    kmax = jnp.where(at_t, tmax[:, :, None, :], kmax)
    return kmin, kmax
