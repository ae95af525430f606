"""Family-agnostic attention layer-core (PR 10).

The per-layer decode bodies that every attention-carrying family shares:
QKV projection, the paged per-layer attention step (gate/metadata
finalize, selection-plan staging, block-sparse decode with quant scales)
and the decode-aux plumbing. ``transformer.lm_decode_step_paged`` scans
``block_decode_paged`` over its self-attention stack; ``hybrid``
(Zamba2-style) scans the SAME body over its shared-attention units with
per-unit page-pool layers — the SeerAttention-R gate is a plug-in over
existing attention, so the serving substrate must not care which family
the attention block lives in.

Both families carry the layer-stacked pools through the layer scan whole
and hand the body a layer index: every pool read and write is at
``[layer, ...]`` of the carried buffer, so the decode step makes no
layer-sized slice, re-layout or restack of a K/V pool (a scan cannot
alias its xs with its ys, so pools passed that way are sliced and
restacked into fresh buffers every step).

Everything here was extracted from ``models.transformer``, and the
decode outputs stay bitwise those the transformer goldens pin;
``transformer`` re-exports these names for backward compatibility.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import attngate as ag
from repro.core import kcache as kc
from repro.core import sparsity as sp
from repro.core.policy import (STAGE_DENSE, STAGE_SELECT, DecodeOptions,
                               SelectionInputs, platform_kernel_impl,
                               select_impl)
from repro.kernels import ops
from repro.models import moe as moe_mod
from repro.models.common import (apply_rope, decode_attention, linear, mlp,
                                 rms_norm)

Params = Dict[str, Any]


def _qkv(p: Params, x: jnp.ndarray, cfg: ModelConfig):
    b, l, _ = x.shape
    dh = cfg.resolved_head_dim
    q = linear(p["wq"], x).reshape(b, l, cfg.n_heads, dh)
    k = linear(p["wk"], x).reshape(b, l, cfg.n_kv_heads, dh)
    v = linear(p["wv"], x).reshape(b, l, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def _policy_active(policy, p: Params) -> bool:
    """Sparse selection runs unless the policy is dense or requires a gate
    the layer doesn't carry (then dense decode — the old ``sparse=True``
    fallback for ungated layers)."""
    return (not policy.dense) and (("gate" in p) or not policy.needs_gate)


def _selection_aux(idx: jnp.ndarray, n_valid: jnp.ndarray, nb: int):
    """Measured per-layer selection telemetry from the ACTUAL selected
    block ids: (sparsity scalar, per-row sparsity [B], mean selected
    blocks [B], visible blocks [B]). The scalar/rows come from
    ``core.sparsity.sparsity_ratio`` on the materialised selection mask."""
    b, hkv, _ = idx.shape
    cnt = jnp.zeros((b, hkv, nb), jnp.int32).at[
        jnp.arange(b)[:, None, None], jnp.arange(hkv)[None, :, None],
        jnp.maximum(idx, 0)].add((idx >= 0).astype(jnp.int32))
    sel_mask = cnt > 0
    rho = sp.sparsity_ratio(sel_mask, n_valid)
    # per-row breakdown: rho is exactly mean(rho_rows) by construction
    sel_counts = jnp.sum(sel_mask, -1).astype(jnp.float32)        # [B,Hkv]
    tot = jnp.maximum(n_valid.astype(jnp.float32), 1.0)
    rho_rows = 1.0 - jnp.mean(sel_counts / tot[:, None], axis=1)
    return rho, rho_rows, jnp.mean(sel_counts, axis=1), \
        n_valid.astype(jnp.float32)


def _dense_aux(new_len: jnp.ndarray, block_size: int):
    """Dense decode reads every visible block: sparsity 0 by definition."""
    n_valid = kc.visible_blocks(jnp.maximum(new_len, 1), block_size)
    nv = n_valid.astype(jnp.float32)
    return (jnp.zeros((), jnp.float32), jnp.zeros_like(nv), nv, nv)


def _zero_layer_aux(batch: int):
    """Per-layer aux when telemetry is compiled out
    (DecodeOptions.measure_sparsity=False)."""
    z = jnp.zeros((batch,), jnp.float32)
    return jnp.zeros((), jnp.float32), z, z, z


def _touched_pages(idx: jnp.ndarray, nb: int) -> jnp.ndarray:
    """Selected block ids [B, Hkv, k] -> touched mask [B, nb] bool: which
    logical blocks ANY head read this layer. The RaaS eviction signal
    (DecodeOptions.track_evictions): the serving engine intersects this
    with its evicted-page mask to detect a selected-but-evicted block
    (fault -> restore -> replay) and feeds it to the BlockHeat recency
    model."""
    b = idx.shape[0]
    cnt = jnp.zeros((b, nb), jnp.int32).at[
        jnp.arange(b)[:, None, None], jnp.maximum(idx, 0)].add(
        (idx >= 0).astype(jnp.int32))
    return cnt > 0


def _dense_touched(new_len: jnp.ndarray, block_size: int, nb: int
                   ) -> jnp.ndarray:
    """Dense decode touches every visible block."""
    vis = kc.visible_blocks(jnp.maximum(new_len, 1), block_size)   # [B]
    return jnp.arange(nb)[None, :] < vis[:, None]


def aggregate_decode_aux(auxs) -> Dict[str, jnp.ndarray]:
    """Stacked per-layer (rho, rho_rows [B], sel [B], vis [B]) -> the
    decode-step aux dict every ModelApi.decode_step returns. A 5th
    element (touched-pages masks [L, B, nb] under
    DecodeOptions.track_evictions) ORs over layers: a block is touched if
    ANY layer's selection read it."""
    rho, rho_rows, sel, vis = auxs[:4]
    out = {"sparsity": jnp.mean(rho),
           "sparsity_rows": jnp.mean(rho_rows, axis=0),
           "sel_blocks": jnp.mean(sel, axis=0),
           "vis_blocks": jnp.mean(vis, axis=0)}
    if len(auxs) > 4:
        out["touched_pages"] = jnp.any(auxs[4], axis=0)
    return out


def zero_decode_aux(batch: int) -> Dict[str, jnp.ndarray]:
    """Aux for attention-free decode paths (SSM): nothing is selected."""
    z = jnp.zeros((batch,), jnp.float32)
    return {"sparsity": jnp.zeros((), jnp.float32), "sparsity_rows": z,
            "sel_blocks": z, "vis_blocks": z}


def attention_decode_paged(p: Params, x1: jnp.ndarray, cfg: ModelConfig, *,
                           pages, layer, page_table, cur_len, active,
                           options: DecodeOptions, budget_blocks=None,
                           shard=None, stage=None, plan=None):
    """One token over paged KV. x1 [S,1,d]; ``pages`` the layer-STACKED
    ``serve.paging.PagedPages`` (HEAD-MAJOR [L, P, Hkv, ps, Dh] K/V) and
    ``layer`` the int32 index of this layer in them; page_table [S, npt];
    cur_len/active [S] per-slot. Returns (out, new pages, aux[, plan]):
    the pools come back whole, written in place at ``[layer, ...]``.

    ``stage``/``plan``: per-layer staging of a step-level SelectionSchedule
    and the carried [S, Hkv, k] plan — same contract as the contiguous
    ``attention_decode``; when ``stage`` is given the return grows a 4th
    element (the next layer's plan) and Kg / min-max metadata page rows
    advance only at selecting layers.

    The gate path is identical to the contiguous ``attention_decode`` —
    same selection, same force-select of the trailing partial block — but
    the Kg cache is the paged twin: ``GatePolicy`` scores it straight off
    ``kg_pages`` through the page table (no per-slot Kg gather on the
    Pallas paths) and the block-sparse attention gathers physical pages
    in-kernel. ``budget_blocks`` [S] (optional, RUNTIME) caps each slot's
    selected list post-hoc — the per-request budget override; forced
    first/last blocks rank ahead of every scored block, so any cap >= the
    forced count preserves them. Rows with ``active == False`` (empty
    decode slots) write to the null page and do not advance.

    ``options.kernel_impl='sharded'`` with a mesh-aware ``shard`` takes
    the paged x sharded path (serve.sharded.sharded_paged_decode): pools
    sharded over kv heads, page table replicated, zero per-step
    collectives — bitwise equal to the unsharded paged step. Requires the
    gate policy; ungated/dense slots fall through to the local paths.

    ``pages.k_scale_pages``/``v_scale_pages`` [L, P, Hkv, 1] f32 (int8
    pools): when present the K/V pools are int8, the trailing
    page is requantized per append (``paging.append_token_paged_quant``)
    and every consumer —
    block-sparse kernels, dense gather fallback, Kg/min-max finalize,
    trailing-block Quest recompute — dequantizes with the scale rows
    (fused in-kernel on the sparse path; no cache-sized fp copy). None
    keeps the fp code path verbatim."""
    from repro.serve import paging as pg
    (k_pages, v_pages, kg_pages, kmin_pages, kmax_pages,
     k_scale, v_scale) = pages
    b = x1.shape[0]
    dh, hkv, g = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.gqa_group
    ps = cfg.gate.block_size
    policy = options.policy
    sparse_on = _policy_active(policy, p)
    q, k, v = _qkv(p, x1, cfg)
    q_nope = q
    pos = cur_len[:, None]                                 # [S,1]
    qr = apply_rope(q, pos, cfg.rope)
    kr = apply_rope(k, pos, cfg.rope)

    mesh = getattr(shard, "mesh", None)
    if sparse_on and options.kernel_impl == "sharded" and mesh is None:
        # fail at trace time with an actionable message instead of a bare
        # ValueError('sharded') from the kernel dispatch deep in the step
        raise ValueError(
            "kernel_impl='sharded' on the paged path needs a mesh-aware "
            "engine: construct DecodeEngine(..., shard=make_shard_fn(mesh))")
    npt = page_table.shape[1]
    # RaaS eviction (ISSUE 7): the page table may hold GHOST ids (>= pool
    # size) for evicted blocks — valid rows of the extended kg/kmin/kmax
    # pools, so SELECTION reads them through the raw table unchanged, but
    # out-of-bounds for the K/V pools. Attention consumers read through a
    # clamped twin; a selected-evicted block is caught by the
    # touched-pages aux and the step replayed after restore.
    pt_kv = (jnp.minimum(page_table, k_pages.shape[1] - 1)
             if options.track_evictions else page_table)

    if sparse_on and options.kernel_impl == "sharded" and policy.needs_gate \
            and "gate" in p:
        from repro.serve.sharded import sharded_paged_decode
        qg = ag.gate_q(p["gate"], q_nope, pos, cfg.gate)[:, 0]  # [S,Hkv,Dg]
        qgrp = qr[:, 0].reshape(b, hkv, g, dh)
        plan_kw = {}
        if stage is not None:
            # DecodeOptions validation pins sharded schedules to
            # select_layer=0 (+ correction layers), so STAGE_DENSE never
            # reaches this body — only fresh-vs-reuse blending remains
            plan_kw = dict(reuse_idx=plan, do_select=(stage == STAGE_SELECT))
        if options.track_evictions:
            plan_kw["pt_kv"] = pt_kv
        o, k_pages, v_pages, kg_pages, k_scale, v_scale, idx = \
            sharded_paged_decode(
                qg, qgrp, kr[:, 0], v[:, 0], k_pages, v_pages, kg_pages,
                layer, page_table, cur_len, active, p["gate"]["wk"],
                mesh=mesh, cfg=cfg.gate, rope=cfg.rope,
                max_selected=options.max_selected(cfg),
                budget_blocks=budget_blocks, split_k=options.split_k,
                inner_impl=platform_kernel_impl(),
                k_scale=k_scale, v_scale=v_scale, **plan_kw)
        new_len = cur_len + active.astype(jnp.int32)
        aux = (_selection_aux(idx, kc.visible_blocks(
                   jnp.maximum(new_len, 1), ps), npt)
               if options.measure_sparsity else _zero_layer_aux(b))
        if options.track_evictions:
            aux = aux + (_touched_pages(idx, npt),)
        out = linear(p["wo"], o.reshape(b, 1, hkv * g * dh))
        ret = (out, pg.PagedPages(k_pages, v_pages, kg_pages, kmin_pages,
                                  kmax_pages, k_scale, v_scale), aux)
        return ret + (idx,) if stage is not None else ret

    staged = stage is not None and sparse_on
    # mirror the contiguous path: the Kg page rows only advance for the
    # policy that reads them (append skips the gate projection on None);
    # under a plan-carrying schedule the advance is further gated to
    # selecting layers (cond on the stage id, below)
    gate_for_append = \
        p.get("gate") if (policy.needs_gate and not staged) else None
    with jax.named_scope("kv_append"):
        if k_scale is not None:
            k_pages, v_pages, kg_pages, k_scale, v_scale = \
                pg.append_token_paged_quant(
                    k_pages, v_pages, kg_pages, k_scale, v_scale, layer,
                    kr[:, 0], v[:, 0], page_table, cur_len, active,
                    gate_for_append, cfg.gate, rope=cfg.rope)
        else:
            k_pages, v_pages, kg_pages = pg.append_token_paged(
                k_pages, v_pages, kg_pages, layer, kr[:, 0], v[:, 0],
                page_table, cur_len, active, gate_for_append, cfg.gate,
                rope=cfg.rope)
        # ... and the min/max metadata page rows only for the policy that
        # reads THEM (QuestPolicy): finalize a page's row when it fills
        if policy.needs_meta and kmin_pages is not None and not staged:
            kmin_pages, kmax_pages = pg.append_meta_paged(
                kmin_pages, kmax_pages, k_pages, layer, page_table, cur_len,
                active, ps, k_scale=k_scale)
    new_len = cur_len + active.astype(jnp.int32)

    if staged:
        # ---- staged path (plan-carrying SelectionSchedule) ------------
        do_select = stage == STAGE_SELECT             # traced bool scalar
        is_dense = stage == STAGE_DENSE

        with jax.named_scope("kv_append"):
            if policy.needs_gate and "gate" in p and kg_pages is not None:
                kg_pages = jax.lax.cond(
                    do_select,
                    lambda kgp: pg.finalize_kg_paged(
                        k_pages, kgp, layer, page_table, cur_len, active,
                        p["gate"], cfg.gate, rope=cfg.rope,
                        k_scale=k_scale),
                    lambda kgp: kgp, kg_pages)
            if policy.needs_meta and kmin_pages is not None:
                def _adv_meta(mn, mx):
                    return pg.append_meta_paged(mn, mx, k_pages, layer,
                                                page_table, cur_len, active,
                                                ps, k_scale=k_scale)
                kmin_pages, kmax_pages = jax.lax.cond(
                    do_select, _adv_meta, lambda mn, mx: (mn, mx),
                    kmin_pages, kmax_pages)

        inp = SelectionInputs(q_nope=q_nope, qr=qr, pos=pos, new_len=new_len,
                              gate_params=p.get("gate"), kg_pages=kg_pages,
                              k_pages=k_pages, page_table=page_table,
                              layer=layer, kmin_pages=kmin_pages,
                              kmax_pages=kmax_pages, k_scale_pages=k_scale)

        def _fresh(cur):
            del cur
            return policy.select(
                inp, cfg, impl=select_impl(options.impl),
                max_selected=options.max_selected(cfg),
                unify_heads=options.schedule.unify_heads).astype(jnp.int32)

        with jax.named_scope("gate_select"):
            idx = jax.lax.cond(do_select, _fresh, lambda cur: cur, plan)
            if budget_blocks is not None:
                # the carried plan is already capped, so re-masking a
                # reuse layer's idx is idempotent
                slot_cap = jnp.arange(idx.shape[-1])[None, None, :] \
                    < budget_blocks[:, None, None]
                idx = jnp.where(slot_cap, idx, -1)
        qgrp = qr[:, 0].reshape(b, hkv, g, dh)

        def _run_sparse(_):
            o = ops.paged_sparse_decode(qgrp, k_pages, v_pages, layer, idx,
                                        pt_kv, new_len, block_size=ps,
                                        impl=options.impl,
                                        k_scales=k_scale, v_scales=v_scale)
            return o.reshape(b, 1, hkv * g, dh)

        def _run_dense(_):
            k_ct = pg.gather_kv(k_pages, layer, pt_kv, k_scale)
            v_ct = pg.gather_kv(v_pages, layer, pt_kv, v_scale)
            return decode_attention(
                qr, k_ct, v_ct, new_len,
                logit_softcap=cfg.attn_logit_softcap).reshape(
                    b, 1, hkv * g, dh)

        with jax.named_scope("sparse_attn"):
            o = jax.lax.cond(is_dense, _run_dense, _run_sparse, None)
        if options.measure_sparsity:
            sel = _selection_aux(idx, kc.visible_blocks(
                jnp.maximum(new_len, 1), ps), npt)
            den = _dense_aux(new_len, ps)
            aux = tuple(jnp.where(is_dense, d, s) for s, d in zip(sel, den))
        else:
            aux = _zero_layer_aux(b)
        if options.track_evictions:
            tch = jnp.where(is_dense, _dense_touched(new_len, ps, npt),
                            _touched_pages(idx, npt))
            aux = aux + (tch,)
        out = linear(p["wo"], o.reshape(b, 1, hkv * g * dh))
        return (out, pg.PagedPages(k_pages, v_pages, kg_pages, kmin_pages,
                                   kmax_pages, k_scale, v_scale), aux, idx)

    if sparse_on:
        inp = SelectionInputs(q_nope=q_nope, qr=qr, pos=pos, new_len=new_len,
                              gate_params=p.get("gate"), kg_pages=kg_pages,
                              k_pages=k_pages, page_table=page_table,
                              layer=layer, kmin_pages=kmin_pages,
                              kmax_pages=kmax_pages, k_scale_pages=k_scale)
        with jax.named_scope("gate_select"):
            idx = policy.select(inp, cfg, impl=select_impl(options.impl),
                                max_selected=options.max_selected(cfg),
                                unify_heads=options.schedule.unify_heads)
            if budget_blocks is not None:
                slot_cap = jnp.arange(idx.shape[-1])[None, None, :] \
                    < budget_blocks[:, None, None]
                idx = jnp.where(slot_cap, idx, -1)
        qgrp = qr[:, 0].reshape(b, hkv, g, dh)
        with jax.named_scope("sparse_attn"):
            o = ops.paged_sparse_decode(qgrp, k_pages, v_pages, layer, idx,
                                        pt_kv, new_len, block_size=ps,
                                        impl=options.impl,
                                        k_scales=k_scale, v_scales=v_scale)
        o = o.reshape(b, 1, hkv * g, dh)
        aux = (_selection_aux(idx, kc.visible_blocks(
                   jnp.maximum(new_len, 1), ps), npt)
               if options.measure_sparsity else _zero_layer_aux(b))
        if options.track_evictions:
            aux = aux + (_touched_pages(idx, npt),)
    else:
        k_ct = pg.gather_kv(k_pages, layer, pt_kv, k_scale)  # [S,Hkv,npt*ps,Dh]
        v_ct = pg.gather_kv(v_pages, layer, pt_kv, v_scale)
        o = decode_attention(qr, k_ct, v_ct, new_len,
                             logit_softcap=cfg.attn_logit_softcap)
        aux = (_dense_aux(new_len, ps) if options.measure_sparsity
               else _zero_layer_aux(b))
        if options.track_evictions:
            aux = aux + (_dense_touched(new_len, ps, npt),)
    out = linear(p["wo"], o.reshape(b, 1, hkv * g * dh))
    ret = (out, pg.PagedPages(k_pages, v_pages, kg_pages, kmin_pages,
                              kmax_pages, k_scale, v_scale), aux)
    # an ungated layer under a plan-carrying schedule: dense fallback, the
    # plan passes through untouched (same contract as attention_decode)
    return ret + (plan,) if stage is not None else ret


def block_decode_paged(p: Params, x1, cfg: ModelConfig, pages, layer,
                       page_table, cur_len, active, *,
                       options: DecodeOptions, budget_blocks=None,
                       shard=None, stage=None, plan=None):
    """One decoder block over the layer-stacked ``pages`` at ``layer``
    (see ``attention_decode_paged``); returns (x1, new pages, aux[, plan])."""
    h = rms_norm(p["ln1"], x1, cfg.norm_eps)
    ret = attention_decode_paged(
        p["attn"], h, cfg, pages=pages, layer=layer, page_table=page_table,
        cur_len=cur_len, active=active, options=options,
        budget_blocks=budget_blocks, shard=shard, stage=stage, plan=plan)
    attn_out, new_pages, aux = ret[:3]
    x1 = x1 + attn_out
    h2 = rms_norm(p["ln2"], x1, cfg.norm_eps)
    with jax.named_scope("mlp"):
        if "moe" in p:
            b = x1.shape[0]
            y, _ = moe_mod.moe_mlp(p["moe"], h2.reshape(b, -1), cfg.moe,
                                   cfg.activation, None)
            y = y.reshape(b, 1, -1)
        else:
            y = mlp(p["mlp"], h2, cfg.activation)
    if stage is not None:
        return x1 + y, new_pages, aux, ret[3]
    return x1 + y, new_pages, aux
