"""K Compression Cache (paper §3.2).

Stores the gate's compressed key representation Kg (post pool + linear +
RoPE) so the K branch never recomputes past blocks. Updated once every
``block_size`` generated tokens; while the trailing block is partial, its
cache entry is stale and the serving engine force-selects the last block.

Memory: nb_max * d_gate per kv head = KV-cache / (block_size * head_dim /
d_gate * 2) — <1% at b=64, d_gate=128 (paper's number).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.config import GateConfig, Rope
from repro.core.attngate import gate_k


class KCompressionCache(NamedTuple):
    kg: jnp.ndarray            # [B, Hkv, nb_max, Dg]  (HEAD-MAJOR)
    n_complete: jnp.ndarray    # [B] int32: number of finalized block entries


def init_kcache(batch: int, max_blocks: int, n_kv_heads: int, d_gate: int,
                dtype=jnp.bfloat16) -> KCompressionCache:
    return KCompressionCache(
        kg=jnp.zeros((batch, n_kv_heads, max_blocks, d_gate), dtype),
        n_complete=jnp.zeros((batch,), jnp.int32))


def prefill_kcache(cache: KCompressionCache, gate_params: Dict[str, Any],
                   k_nope: jnp.ndarray, cfg: GateConfig) -> KCompressionCache:
    """Bulk-populate from a prefill of S tokens (only complete blocks).
    k_nope is seq-major [B, S, Hkv, Dh] (the natural prefill activation
    layout); the one-time transpose into the head-major cache happens here
    — prefill owns the layout conversion, decode never does."""
    b, s, hkv, dh = k_nope.shape
    nb = s // cfg.block_size
    if nb == 0:
        return cache
    kg = gate_k(gate_params, k_nope[:, : nb * cfg.block_size], cfg)
    new = cache.kg.at[:, :, :nb].set(
        jnp.swapaxes(kg, 1, 2).astype(cache.kg.dtype))
    return KCompressionCache(new, jnp.full((b,), nb, jnp.int32))


def finalize_block_kg(gate_params: Dict[str, Any], blk: jnp.ndarray,
                      start_pos, block_index, cfg: GateConfig, *,
                      is_roped: bool, rope: Optional[Rope]
                      ) -> jnp.ndarray:
    """One COMPLETE block of keys [block_size, Hkv, Dh] -> Kg row [Hkv, Dg].

    The single source of truth for block finalization, shared by the
    contiguous decode update (below) and the paged cache
    (serve.paging.append_token_paged) so the two can never drift. When
    ``is_roped`` the stored keys are rotated back to the pre-rope frame
    first (RoPE is an orthogonal rotation: inversion = apply with negated
    positions), avoiding a second pre-rope K cache just for the gate;
    ``rope`` is the model's RoPE that rotated them, scaling included.
    """
    from repro.models.common import apply_rope
    if is_roped:
        pos = -(start_pos + jnp.arange(blk.shape[0]))
        blk = apply_rope(blk[None], pos[None], rope)[0]
    return gate_k(gate_params, blk[None], cfg,
                  first_block_index=block_index)[0, 0]


def update_kcache(cache: KCompressionCache, gate_params: Dict[str, Any],
                  k_cache_raw: jnp.ndarray, cur_len: jnp.ndarray,
                  cfg: GateConfig, *, cache_is_roped: bool = False,
                  rope: Optional[Rope] = None) -> KCompressionCache:
    """Decode-time incremental update.

    k_cache_raw: [B, Hkv, S_max, Dh] HEAD-MAJOR key cache. If
    ``cache_is_roped`` the stored keys are post-RoPE (the standard layout)
    and are rotated *back* to the pre-rope frame with ``rope`` (the model's
    RoPE, scaling included) before pooling (RoPE is an
    orthogonal rotation, so inversion = apply with negated positions) —
    this avoids keeping a second pre-rope K cache (2x memory) just for the
    gate. Only ONE block-size slice of the cache is ever touched per step.
    cur_len: [B] sequence length *after* appending the newest token.

    When ``cur_len`` crosses a block boundary, the just-completed block of
    ``block_size`` raw keys is pooled+projected and written at slot
    ``cur_len // block_size - 1``. Uniform-length batches share one boundary
    check; ragged batches are handled per-row via where-masking.
    """
    bs = cfg.block_size
    # cur_len == 0 (empty/retired slot) must NOT count as a completed
    # block: (0 % bs) == 0 used to write a garbage Kg row at slot 0 and
    # set n_complete = 1 (ISSUE 5 satellite)
    completed = ((cur_len % bs) == 0) & (cur_len > 0)     # [B] bool
    blk_idx = jnp.maximum(cur_len // bs - 1, 0)           # [B]
    start = blk_idx * bs

    def one_row(k_raw, st, bi):
        # k_raw [Hkv, S, Dh]: slice the completed block, flip the tiny
        # [Hkv, bs] corner to the seq-major frame finalize expects
        blk = jax.lax.dynamic_slice_in_dim(k_raw, st, bs, axis=1)
        return finalize_block_kg(gate_params, jnp.swapaxes(blk, 0, 1), st,
                                 bi, cfg, is_roped=cache_is_roped,
                                 rope=rope)    # [Hkv, Dg]

    kg_new = jax.vmap(one_row)(k_cache_raw, start, blk_idx)   # [B,Hkv,Dg]
    cur = jax.vmap(lambda c, i: c[:, i])(cache.kg, blk_idx)   # current content
    kg_write = jnp.where(completed[:, None, None], kg_new.astype(cache.kg.dtype), cur)
    new_kg = jax.vmap(lambda c, i, v: c.at[:, i].set(v))(cache.kg, blk_idx,
                                                         kg_write)
    new_n = jnp.where(completed, blk_idx + 1, cache.n_complete)
    return KCompressionCache(new_kg, new_n.astype(jnp.int32))


def visible_blocks(cur_len: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """Number of selectable blocks = ceil(cur_len / block_size); the last one
    may be partial (stale cache entry) and is force-selected upstream."""
    return -(-cur_len // block_size)
