"""Sequence-parallel sparse flash decoding (shard_map, explicit collectives).

The paper's kernel splits the *selected* KV blocks over SMs (num_split) and
combines online-softmax partials. Across TPU chips the same idea becomes:

  * KV cache + K-compression cache sharded along the SEQUENCE dim over the
    'model' axis (plus the DP axes when batch is unshardable — long_500k);
  * each shard scores its local gate blocks, takes a local top-c candidate
    list, and the budget's global top-k is resolved with ONE small
    all-gather of candidate scores (hierarchical exact top-k);
  * each shard runs block-sparse attention over its own selected blocks
    only (gathered from the LOCAL cache shard — no cross-chip KV movement);
  * partials (o_i, m_i, l_i) merge with the flash-decoding rescale:
        m = pmax(m_i),  l = psum(l_i e^{m_i-m}),  o = psum(o_i e^{m_i-m})/l.
  * the new token's K/V (and the completed block's Kg entry) are written by
    the OWNING shard only.

Collective payload per layer step: all-gather of [B,Hkv,c] scores + psum of
[B,Hkv,G,Dh]+[B,Hkv,G,2] partials — KBs/step instead of the GBs/step that
GSPMD's resharding of a gathered KV cache costs (EXPERIMENTS.md §Perf).

Load balance: the paper splits the selected list evenly; with a sharded
cache a shard can own at most ``c = ceil(k/nshards * local_cap_factor)``
selected blocks (static shape). Score-ordered overflow beyond c is dropped;
with the default factor 2 this only triggers when >2x of the budget
concentrates in one shard (recall impact measured in benchmarks).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import GateConfig, Rope
from repro.core import sparsity as sp
from repro.core.policy import select_impl
from repro.distributed.sharding import MODEL
from repro.models.common import NEG_INF, apply_rope


def _flat_axis_index(axes: Tuple[str, ...], sizes: Tuple[int, ...]):
    idx = jnp.int32(0)
    for a, s in zip(axes, sizes):
        idx = idx * s + jax.lax.axis_index(a)
    return idx


def sharded_sparse_decode(
        qg: jnp.ndarray,          # [B, Hkv, Dg]    gate query (post-rope)
        qr: jnp.ndarray,          # [B, Hkv, G, Dh] attention query (post-rope)
        kr_new: jnp.ndarray,      # [B, Hkv, Dh]    new key (post-rope)
        v_new: jnp.ndarray,       # [B, Hkv, Dh]
        k_cache: jnp.ndarray,     # [B, Hkv, S, Dh] head-major, seq-sharded
        v_cache: jnp.ndarray,
        kg_cache: jnp.ndarray,    # [B, Hkv, nb, Dg] head-major, seq-sharded
        cur_len: jnp.ndarray,     # [B] length BEFORE this token
        gate_wk: jnp.ndarray,     # [Hkv, 3*Dh, Dg]
        *,
        mesh: Mesh,
        seq_axes: Tuple[str, ...],
        batch_spec,
        cfg: GateConfig,
        rope: Rope,               # the model's RoPE, for the Kg un-rope
        max_selected: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One decode step for ONE layer. ``max_selected`` overrides the
    config block budget (DecodeOptions.budget_override). Returns
    (o [B,Hkv,G,Dh], k_cache, v_cache, kg_cache, n_sel [B,Hkv]) with the
    caches updated in place (same shardings); ``n_sel`` is the psum'd
    per-(row, kv-head) count of selected blocks across shards (measured
    sparsity telemetry).
    """
    sizes = tuple(int(mesh.shape[a]) for a in seq_axes)
    nsh = 1
    for s in sizes:
        nsh *= s
    bs = cfg.block_size
    k_budget = sp.resolve_max_selected(cfg, max_selected)
    cap = max(1, min(int(math.ceil(k_budget / nsh * cfg.local_cap_factor)),
                     k_cache.shape[2] // (bs * nsh)))

    bspec = batch_spec
    seq = tuple(seq_axes) if len(seq_axes) > 1 else seq_axes[0]
    spec_q = P(bspec, None, None, None)       # qr [B,Hkv,G,Dh]
    spec_qg = P(bspec, None, None)
    spec_kv = P(bspec, None, seq, None)       # head-major: seq is axis 2
    spec_len = P(bspec)
    spec_w = P(None, None, None)

    def local(qg, qr, kr_new, v_new, k_loc, v_loc, kg_loc, cur_len, wk):
        b, hkv, s_loc, dh = k_loc.shape
        nb_loc = kg_loc.shape[2]
        dg = qg.shape[-1]
        ax = _flat_axis_index(seq_axes, sizes)
        tok0 = ax * s_loc                                  # global token base
        blk0 = ax * nb_loc                                 # global block base
        new_len = cur_len + 1                              # [B]
        bidx = jnp.arange(b)

        # -- 1) KV write by the owning shard ------------------------------
        own_tok = (cur_len >= tok0) & (cur_len < tok0 + s_loc)
        lpos = jnp.clip(cur_len - tok0, 0, s_loc - 1)
        cur_k = k_loc[bidx, :, lpos]
        cur_v = v_loc[bidx, :, lpos]
        k_loc = k_loc.at[bidx, :, lpos].set(
            jnp.where(own_tok[:, None, None], kr_new, cur_k))
        v_loc = v_loc.at[bidx, :, lpos].set(
            jnp.where(own_tok[:, None, None], v_new, cur_v))

        # -- 2) Kg write when a block completes ---------------------------
        completed = (new_len % bs) == 0
        gblk = jnp.maximum(new_len // bs - 1, 0)           # [B] global block
        own_blk = (gblk >= blk0) & (gblk < blk0 + nb_loc) & completed
        lblk = jnp.clip(gblk - blk0, 0, nb_loc - 1)
        lstart = lblk * bs

        def kg_row(k_row, st, gb):
            # k_row head-major [Hkv, s_loc, Dh]: slice the block, flip the
            # tiny [Hkv, bs] corner to seq-major for pooling
            blk = jax.lax.dynamic_slice_in_dim(k_row, st, bs, axis=1)
            blk = jnp.swapaxes(blk, 0, 1)                  # [bs, Hkv, Dh]
            pos = -(tok0 + st + jnp.arange(bs))            # un-rope
            blk = apply_rope(blk[None], pos[None], rope)[0]
            pooled = jnp.concatenate(
                [jnp.max(blk, 0), jnp.min(blk, 0),
                 jnp.mean(blk.astype(jnp.float32), 0).astype(blk.dtype)], -1)
            kg = jnp.einsum("he,hed->hd", pooled, wk)      # [Hkv, Dg]
            if cfg.use_rope:
                kg = apply_rope(kg[None, None], (gb * bs)[None, None],
                                cfg.rope)[0, 0]
            return kg

        kg_new = jax.vmap(kg_row)(k_loc, lstart, gblk)     # [B,Hkv,Dg]
        cur_kg = kg_loc[bidx, :, lblk]
        kg_loc = kg_loc.at[bidx, :, lblk].set(
            jnp.where(own_blk[:, None, None],
                      kg_new.astype(kg_loc.dtype), cur_kg))

        # -- 3) local gate scores + candidates ----------------------------
        gid = blk0 + jnp.arange(nb_loc)                    # global block ids
        n_valid = -(-new_len // bs)                        # [B]
        s_gate = jnp.einsum("bhd,bhnd->bhn", qg.astype(jnp.float32),
                            kg_loc.astype(jnp.float32)) / math.sqrt(dg)
        vis = gid[None, None, :] < n_valid[:, None, None]
        s_raw = jnp.where(vis, s_gate, NEG_INF)            # unforced scores
        big = jnp.float32(1e30)
        s_gate = s_raw
        if cfg.always_last_block:
            s_gate = jnp.where(
                gid[None, None, :] == (n_valid - 1)[:, None, None], big, s_gate)
        if cfg.always_first_block:
            s_gate = jnp.where(gid[None, None, :] == 0, big, s_gate)
        c = min(cap, nb_loc)
        cand_v, cand_i = jax.lax.top_k(s_gate, c)          # [B,Hkv,c] local

        if cfg.method == "threshold":
            # -- 4t) distributed softmax threshold (paper §3.1) ----------
            # softmax stats over the UNFORCED scores (forcing would skew
            # the normalizer); forced candidates pass unconditionally
            gm = jnp.max(s_raw, axis=-1, keepdims=True)
            gm = jax.lax.pmax(gm, seq) if nsh > 1 else gm
            gl = jnp.sum(jnp.where(vis, jnp.exp(s_raw - gm), 0.0),
                         axis=-1, keepdims=True)
            gl = jax.lax.psum(gl, seq) if nsh > 1 else gl
            cand_raw = jnp.take_along_axis(s_raw, cand_i, axis=-1)
            probs = jnp.exp(cand_raw - gm) / jnp.maximum(gl, 1e-30)
            mine = ((probs > cfg.threshold) | (cand_v > 1e29)) \
                & (cand_raw > NEG_INF / 2)
        else:
            # -- 4) hierarchical exact top-k ------------------------------
            if nsh > 1:
                allv = jax.lax.all_gather(cand_v, seq, axis=0, tiled=False)
                allv = jnp.moveaxis(allv.reshape((nsh,) + cand_v.shape), 0, -2)
                allv = allv.reshape(cand_v.shape[:-1] + (nsh * c,))
            else:
                allv = cand_v
            kk = min(k_budget, allv.shape[-1])
            thr = jax.lax.top_k(allv, kk)[0][..., -1:]     # [B,Hkv,1]
            mine = (cand_v >= thr) & (cand_v > NEG_INF / 2)  # [B,Hkv,c]

        # -- 5) local block-sparse attention ------------------------------
        # gather straight off the native head-major [B,Hkv,S,Dh] layout:
        # the selected blocks are the ONLY cache bytes touched this step
        lsel = cand_i                                       # local block ids
        pos_l = lsel[..., None] * bs + jnp.arange(bs)       # [B,Hkv,c,bs]
        gpos = pos_l.reshape(b, hkv, c * bs)
        kg_ = jnp.take_along_axis(k_loc, gpos[..., None], axis=2)
        vg_ = jnp.take_along_axis(v_loc, gpos[..., None], axis=2)
        sc = jnp.einsum("bhgd,bhkd->bhgk", qr.astype(jnp.float32),
                        kg_.astype(jnp.float32)) * (1.0 / math.sqrt(dh))
        tok_valid = (tok0 + pos_l) < new_len[:, None, None, None]
        valid = mine[..., None] & tok_valid                 # [B,Hkv,c,bs]
        valid = valid.reshape(b, hkv, 1, c * bs)
        sc = jnp.where(valid, sc, NEG_INF)

        # -- 6) flash-decoding combine across shards ----------------------
        # Two-pass form: resolve the GLOBAL max first (pmax is exact), then
        # every shard exponentiates against it and normalises by the global
        # psum'd mass before the PV product. Each per-element op is then
        # bitwise identical to the single-device softmax reference — the
        # one-pass exp(m_i-m) rescale drifts ~1e-5 per step, and a decode
        # loop amplifies any bf16 rounding flip through the KV cache
        # (observed 4e-2 logit divergence by step 4; see test_distributed).
        m_i = jnp.max(sc, axis=-1, keepdims=True)           # [B,Hkv,G,1]
        m = jax.lax.pmax(m_i, seq) if nsh > 1 else m_i
        p = jnp.where(valid, jnp.exp(sc - m), 0.0)
        l_i = jnp.sum(p, axis=-1, keepdims=True)
        l = jax.lax.psum(l_i, seq) if nsh > 1 else l_i
        pn = p / jnp.maximum(l, 1e-30)
        o_i = jnp.einsum("bhgk,bhkd->bhgd", pn, vg_.astype(jnp.float32))
        o = jax.lax.psum(o_i, seq) if nsh > 1 else o_i

        # measured selection count: each shard counts its own winners
        n_sel = jnp.sum(mine.astype(jnp.int32), axis=-1)    # [B,Hkv] local
        n_sel = jax.lax.psum(n_sel, seq) if nsh > 1 else n_sel
        return o.astype(qr.dtype), k_loc, v_loc, kg_loc, n_sel

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_qg, spec_q, P(bspec, None, None), P(bspec, None, None),
                  spec_kv, spec_kv, spec_kv, spec_len, spec_w),
        out_specs=(spec_q, spec_kv, spec_kv, spec_kv, P(bspec, None)))
    return fn(qg, qr, kr_new, v_new, k_cache, v_cache, kg_cache, cur_len,
              gate_wk)


# ---------------------------------------------------------------------------
# paged x sharded: head-sharded page pools (ISSUE 4)
# ---------------------------------------------------------------------------

def sharded_paged_decode(
        qg: jnp.ndarray,          # [S, Hkv, Dg]     gate query (post-rope)
        qgrp: jnp.ndarray,        # [S, Hkv, G, Dh]  attention query grouped
        kr_new: jnp.ndarray,      # [S, Hkv, Dh]     new key (post-rope)
        v_new: jnp.ndarray,       # [S, Hkv, Dh]
        k_pages: jnp.ndarray,     # [L, P, Hkv, ps, Dh] stacked pool
        v_pages: jnp.ndarray,
        kg_pages: jnp.ndarray,    # [L, P, Hkv, Dg]
        layer: jnp.ndarray,       # [] int32 layer index (replicated)
        page_table: jnp.ndarray,  # [S, npt] int32   (replicated)
        cur_len: jnp.ndarray,     # [S] length BEFORE this token
        active: jnp.ndarray,      # [S] bool
        gate_wk: jnp.ndarray,     # [Hkv, 3*Dh, Dg]
        *,
        mesh: Mesh,
        cfg: GateConfig,
        rope: Rope,               # the model's RoPE, for the Kg un-rope
        max_selected: Optional[int] = None,
        budget_blocks: Optional[jnp.ndarray] = None,
        split_k: int = 1,
        inner_impl: str = "ref",
        reuse_idx: Optional[jnp.ndarray] = None,   # [S, Hkv, k] carried plan
        do_select: Optional[jnp.ndarray] = None,   # [] bool: fresh vs reuse
        pt_kv: Optional[jnp.ndarray] = None,       # [S, npt] clamped table
        k_scale: Optional[jnp.ndarray] = None,     # [L, P, Hkv, 1] scales
        v_scale: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, ...]:
    """One PAGED decode step for ONE layer on a sharded mesh.

    The pools are the layer-STACKED, head-sharded ones
    (``distributed.sharding.paged_pool_pspecs``) and ``layer`` picks the
    layer: the shard body appends and reads at ``[layer, ...]`` of its
    local head slice, so the layer loop carries the pools whole and
    writes them in place.

    Composition rule (the paged x sharded design): the page POOLS (and the
    Kg pool, and the gate weights, and the per-head queries) are sharded
    over the KV-HEAD axis on 'model'; the page TABLE, per-slot lengths and
    the active mask are replicated. Per-kv-head attention is independent —
    selection, the paged append (including the Kg finalization of a
    completed page) and the block-sparse attention all batch over heads —
    so every shard runs the IDENTICAL unsharded math on its local head
    slice and the step needs ZERO collectives: the out-specs concatenate
    the head shards back. This is why paged x sharded is bitwise equal to
    paged-unsharded (tested), unlike the sequence-sharded contiguous path
    whose flash combine reorders the softmax reduction.

    Within a shard the selected list is reduced by the split-K kernel when
    ``split_k > 1`` (``ops.paged_sparse_decode_splitk``) — the in-shard
    analog of the paper's num_split — with ``inner_impl`` picking the
    selection and attention kernels each shard runs (the platform's:
    ``core.policy.platform_kernel_impl``).

    Returns (o [S,Hkv,G,Dh], k_pages, v_pages, kg_pages, k_scale, v_scale,
    idx [S,Hkv,k]) with pools updated in place (same shardings); ``idx``
    is the gathered selection for telemetry; the scale slots pass through
    as None on fp pools.

    ``k_scale``/``v_scale`` [L, P, Hkv, 1] f32 (int8 pools): the
    dequant scale rows, sharded over KV heads exactly like the Kg pool
    (``spec_pool4``) — the per-head quantization axis is
    what makes int8 pools compose with head sharding for free. The shard
    body swaps the append for ``paging.append_token_paged_quant`` and
    threads the scales into the block-sparse kernels (fused dequant);
    still zero per-step collectives, and None keeps the fp body verbatim.

    ``reuse_idx``/``do_select`` (step-level SelectionSchedule): when given,
    the step blends ``jnp.where(do_select, fresh, reuse_idx)`` INSIDE the
    shard body, before the budget cap — on a reuse layer the carried plan
    drives the block-sparse attention and the returned ``idx`` is the plan.
    The fresh selection (and the Kg page finalize) still runs every layer
    on this path: the blend keeps the budgeted/unbudgeted one-compiled-
    program property and the bitwise paged==paged x sharded contract, at
    the cost of not saving the gate score here (the reuse win on this path
    is accuracy-surface parity with the local paths, not selection FLOPs).

    ``pt_kv`` (RaaS eviction, ISSUE 7): a clamped twin of the page table
    used ONLY by the block-sparse K/V attention gather. Under eviction the
    raw table may hold ghost ids (>= pool size, valid in the EXTENDED kg
    pool only) — selection and the trailing-page append keep reading the
    raw table (ghost rows shard over heads exactly like physical rows, and
    the trailing page is pinned resident), while attention reads in-bounds
    through the clamp; a selected-evicted block is replayed by the engine
    after restore. Replicated like the table itself, so the
    zero-collectives property is untouched. None = the raw table
    (pre-eviction behavior, bitwise unchanged).
    """
    from repro.core import kcache as kc
    from repro.kernels import ops
    from repro.serve import paging as pg

    hkv = qg.shape[1]
    nsh = int(mesh.shape[MODEL])
    if hkv % nsh:
        raise ValueError(
            f"paged sharded decode: n_kv_heads={hkv} not divisible by "
            f"mesh axis '{MODEL}' of size {nsh}")
    if budget_blocks is None:
        # never-binding sentinel: masking with it is the identity, so the
        # budgeted and unbudgeted paths stay one compiled program
        budget_blocks = jnp.full((qg.shape[0],), 2 ** 30, jnp.int32)

    # pin the per-token operands REPLICATED: without this GSPMD propagates
    # the head-sharding backwards into the producing qkv/gate projection
    # dots, retiling them (different contraction order -> last-bit drift)
    # and breaking the bitwise paged==paged x sharded contract; with it the
    # projections compute exactly the unsharded program and the boundary
    # reshard is an exact slice
    rep = NamedSharding(mesh, P())
    qg, qgrp, kr_new, v_new = (
        jax.lax.with_sharding_constraint(x, rep)
        for x in (qg, qgrp, kr_new, v_new))
    if reuse_idx is not None:
        # the plan was gathered replicated on the producing layer; pin it
        # so the head-axis reshard below is an exact slice
        reuse_idx = jax.lax.with_sharding_constraint(reuse_idx, rep)

    spec_h3 = P(None, MODEL, None)
    spec_h4 = P(None, MODEL, None, None)
    spec_pool4 = P(None, None, MODEL, None)          # [L, P, Hkv, Dg|1]
    spec_pool5 = P(None, None, MODEL, None, None)    # [L, P, Hkv, ps, Dh]
    rep1, rep2 = P(None), P(None, None)

    if pt_kv is None:
        pt_kv = page_table
    quant = k_scale is not None

    def local(qg, qgrp, kr_new, v_new, kp, vp, kgp, ly, pt, ptk, cl, act,
              bb, wk, *extra):
        extra = list(extra)
        if quant:
            ksc, vsc = extra[0], extra[1]
            extra = extra[2:]
            kp, vp, kgp, ksc, vsc = pg.append_token_paged_quant(
                kp, vp, kgp, ksc, vsc, ly, kr_new, v_new, pt, cl, act,
                {"wk": wk}, cfg, rope=rope)
        else:
            ksc = vsc = None
            kp, vp, kgp = pg.append_token_paged(
                kp, vp, kgp, ly, kr_new, v_new, pt, cl, act, {"wk": wk},
                cfg, rope=rope)
        new_len = cl + act.astype(jnp.int32)
        n_valid = kc.visible_blocks(jnp.maximum(new_len, 1), cfg.block_size)
        # this layer's Kg rows as a slice, as GatePolicy reads them
        idx = ops.gate_select_paged(qg, kgp[ly], pt, n_valid, cfg,
                                    max_selected,
                                    impl=select_impl(inner_impl))
        if extra:
            reuse, do_sel = extra
            idx = jnp.where(do_sel, idx, reuse)
        cap = jnp.arange(idx.shape[-1])[None, None, :] < bb[:, None, None]
        idx = jnp.where(cap, idx, -1)
        if split_k > 1:
            o = ops.paged_sparse_decode_splitk(
                qgrp, kp, vp, ly, idx, ptk, new_len,
                block_size=cfg.block_size, num_splits=split_k,
                impl=inner_impl, k_scales=ksc, v_scales=vsc)
        else:
            o = ops.paged_sparse_decode(qgrp, kp, vp, ly, idx, ptk, new_len,
                                        block_size=cfg.block_size,
                                        impl=inner_impl,
                                        k_scales=ksc, v_scales=vsc)
        out = (o, kp, vp, kgp) + ((ksc, vsc) if quant else ()) + (idx,)
        return out

    in_specs = (spec_h3, spec_h4, spec_h3, spec_h3, spec_pool5, spec_pool5,
                spec_pool4, P(), rep2, rep2, rep1, rep1, rep1,
                P(MODEL, None, None))
    args = (qg, qgrp, kr_new, v_new, k_pages, v_pages, kg_pages,
            jnp.asarray(layer, jnp.int32), page_table, pt_kv, cur_len,
            active, budget_blocks, gate_wk)
    if quant:
        in_specs = in_specs + (spec_pool4, spec_pool4)
        args = args + (k_scale, v_scale)
    if reuse_idx is not None:
        in_specs = in_specs + (spec_h3, P())
        args = args + (reuse_idx, jnp.asarray(do_select, bool))
    out_specs = (spec_h4, spec_pool5, spec_pool5, spec_pool4) \
        + ((spec_pool4, spec_pool4) if quant else ()) + (spec_h3,)
    # the replication check stays on: the per-shard Pallas kernels declare
    # the mesh axes their outputs vary over (kernels' ``out_vma``)
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs)
    out = fn(*args)
    if quant:
        o, k_pages, v_pages, kg_pages, k_scale, v_scale, idx = out
    else:
        o, k_pages, v_pages, kg_pages, idx = out
    # gather o/idx back to replicated (an exact all-gather) BEFORE they
    # feed dense compute: a head-sharded o would make GSPMD partition the
    # wo projection's contraction dim (psum -> reordered reduction ->
    # last-bit drift); the pools stay head-sharded for the next step
    o = jax.lax.with_sharding_constraint(o, rep)
    idx = jax.lax.with_sharding_constraint(idx, rep)
    return o, k_pages, v_pages, kg_pages, k_scale, v_scale, idx
