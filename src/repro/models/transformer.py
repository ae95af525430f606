"""Decoder (and encoder) transformer LM with SeerAttention-R gates.

Covers families: dense, moe, vlm (cross-attn units), audio (encoder-only).
SSM/hybrid live in repro.models.mamba / repro.models.hybrid.

Layers are stacked and `lax.scan`ned (HLO stays compact at 61L/1T scale);
remat policy from cfg. All forward fns are pure; params are dict pytrees.

Modes:
  lm_forward(..., mode="pretrain")  -> logits + CE-ready
  lm_forward(..., mode="distill")   -> per-layer gate KL (base frozen; the
                                       caller differentiates wrt gate params)
  lm_prefill / lm_decode_step       -> serving with KV + K-compression cache
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import attngate as ag
from repro.core import kcache as kc
from repro.core import metacache as mc
from repro.core.distill import gate_kl_loss, ground_truth_from_blockmax
from repro.core.policy import (STAGE_DENSE, STAGE_SELECT, DecodeOptions,
                               SelectionInputs, default_options, select_impl,
                               selection_width)
from repro.kernels import ops
from repro.models import moe as moe_mod
# the per-layer paged attention body + decode-aux helpers live in the
# family-agnostic layer-core (PR 10) — re-exported here so existing
# importers (ssm_lm, hybrid, tests) keep working
from repro.models.attn_core import (_dense_aux, _dense_touched,
                                    _policy_active, _qkv, _selection_aux,
                                    _touched_pages, _zero_layer_aux,
                                    aggregate_decode_aux,
                                    attention_decode_paged,
                                    block_decode_paged, zero_decode_aux)
from repro.models.common import (NEG_INF, apply_rope, chunked_attention,
                                 cross_entropy_loss, decode_attention,
                                 init_linear, init_mlp, init_rmsnorm,
                                 layer_scan, linear, mlp, rms_norm)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, *, with_gate: bool,
                   cross: bool = False) -> Params:
    dh = cfg.resolved_head_dim
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_model
    ks = jax.random.split(key, 5)
    p: Params = {
        "wq": init_linear(ks[0], d, h * dh, cfg.dtype),
        "wk": init_linear(ks[1], d, hkv * dh, cfg.dtype),
        "wv": init_linear(ks[2], d, hkv * dh, cfg.dtype),
        "wo": init_linear(ks[3], h * dh, d, cfg.dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(dh, cfg.dtype)
        p["k_norm"] = init_rmsnorm(dh, cfg.dtype)
    if with_gate and not cross:
        p["gate"] = ag.init_attngate(
            ks[4], n_kv_heads=hkv, group=cfg.gqa_group, head_dim=dh,
            cfg=cfg.gate, dtype=cfg.dtype)
    return p


def init_block(key, cfg: ModelConfig, *, with_gate: bool,
               cross: bool = False) -> Params:
    k1, k2 = jax.random.split(key)
    p: Params = {
        "ln1": init_rmsnorm(cfg.d_model, cfg.dtype),
        "ln2": init_rmsnorm(cfg.d_model, cfg.dtype),
        "attn": init_attention(k1, cfg, with_gate=with_gate, cross=cross),
    }
    if cfg.family == "moe" and not cross:
        p["moe"] = moe_mod.init_moe(k2, cfg.d_model, cfg.moe,
                                    cfg.activation, cfg.dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.activation, cfg.dtype)
    return p


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_lm(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    p: Params = {}
    if cfg.family == "audio":
        p["in_proj"] = init_linear(ks[0], cfg.n_audio_features, cfg.d_model,
                                   cfg.dtype)
        p["embed"] = {"w": (jax.random.normal(ks[4], (cfg.vocab_size, cfg.d_model),
                                              jnp.float32) * 0.02).astype(jnp.dtype(cfg.dtype))}
    else:
        p["embed"] = {"w": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                              jnp.float32) * 0.02).astype(jnp.dtype(cfg.dtype))}

    gate_on = cfg.gate.enabled and cfg.has_attention and cfg.is_decoder
    if cfg.cross_attn_period:
        period = cfg.cross_attn_period
        n_units = cfg.num_layers // period
        n_self = period - 1

        def unit_self(k):
            return _stack_init(lambda kk: init_block(kk, cfg, with_gate=gate_on),
                               k, n_self)
        p["blocks"] = _stack_init(unit_self, ks[1], n_units)
        p["cross_blocks"] = _stack_init(
            lambda k: init_block(k, cfg, with_gate=False, cross=True),
            ks[2], n_units)
    else:
        p["blocks"] = _stack_init(
            lambda k: init_block(k, cfg, with_gate=gate_on),
            ks[1], cfg.num_layers)
    p["final_norm"] = init_rmsnorm(cfg.d_model, cfg.dtype)
    if not cfg.tie_embeddings:
        p["lm_head"] = init_linear(ks[3], cfg.d_model, cfg.vocab_size, cfg.dtype)
    return p


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def attention_full(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                   rope_positions: jnp.ndarray,
                   segment_ids: Optional[jnp.ndarray],
                   distill: bool, collect_cache: bool,
                   collect_gate: bool = False):
    """Returns (out, kl_loss, cache_tuple|None).

    ``collect_gate`` (requires distill): the cache slot instead carries
    {"glog", "gt", "qr", "kr"} for gate-quality evaluation (benchmarks).
    """
    b, l, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    q_nope, k_nope = q, k
    qr = apply_rope(q, rope_positions, cfg.rope)
    kr = apply_rope(k, rope_positions, cfg.rope)

    gate_on = distill and "gate" in p
    gt_bs = cfg.gate.block_size if gate_on else 0
    o, bm = chunked_attention(
        qr, kr, v, causal=cfg.causal, q_chunk=cfg.q_chunk,
        logit_softcap=cfg.attn_logit_softcap, gt_block_size=gt_bs,
        segment_ids=segment_ids, unroll_chunks=not cfg.scan_layers)

    kl = jnp.zeros((), jnp.float32)
    glog = gt = None
    if gate_on:
        gt = ground_truth_from_blockmax(jax.lax.stop_gradient(bm), cfg.gqa_group)
        qg = ag.gate_q(p["gate"], jax.lax.stop_gradient(q_nope),
                       rope_positions, cfg.gate)
        kg = ag.gate_k(p["gate"], jax.lax.stop_gradient(k_nope), cfg.gate)
        glog = ag.gate_logits(qg, kg)                     # [B,Hkv,L,nb]
        mask = ag.block_causal_mask(jnp.arange(l), kg.shape[1],
                                    cfg.gate.block_size)
        glog = jnp.where(mask[None, None], glog, NEG_INF)
        kl = gate_kl_loss(glog, gt)

    cache = None
    if collect_gate and gate_on:
        cache = {"glog": glog, "gt": gt, "qr": qr, "kr": kr}
    elif collect_cache:
        # only COMPLETE blocks enter the K-compression cache (ragged
        # prompts: the trailing partial block stays stale-until-complete,
        # same contract as kcache.prefill_kcache)
        nb_full = (l // cfg.gate.block_size) * cfg.gate.block_size
        kg_full = (ag.gate_k(p["gate"], k_nope[:, :nb_full], cfg.gate)
                   if "gate" in p else None)
        cache = (kr, v, kg_full)
    return linear(p["wo"], o.reshape(b, l, -1)), kl, cache


def cross_attention_full(p: Params, x: jnp.ndarray, ctx: jnp.ndarray,
                         cfg: ModelConfig):
    """Cross-attn into a fixed context (stub image embeddings). No RoPE on
    the context side; queries use their own positions implicitly via the
    self-attn layers, so cross-attn here is position-free (Flamingo-style)."""
    b, l, _ = x.shape
    dh = cfg.resolved_head_dim
    q = linear(p["wq"], x).reshape(b, l, cfg.n_heads, dh)
    k = linear(p["wk"], ctx).reshape(b, ctx.shape[1], cfg.n_kv_heads, dh)
    v = linear(p["wv"], ctx).reshape(b, ctx.shape[1], cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rms_norm(p["q_norm"], q, cfg.norm_eps)
        k = rms_norm(p["k_norm"], k, cfg.norm_eps)
    o, _ = chunked_attention(q, k, v, causal=False, q_chunk=cfg.q_chunk,
                             unroll_chunks=not cfg.scan_layers)
    return linear(p["wo"], o.reshape(b, l, -1))


def block_fwd_full(p: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                   rope_positions, segment_ids, distill: bool,
                   collect_cache: bool = False, collect_gate: bool = False,
                   cross_ctx=None, is_cross: bool = False, shard=None):
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    if is_cross:
        attn_out = cross_attention_full(p["attn"], h, cross_ctx, cfg)
        kl, cache = jnp.zeros((), jnp.float32), None
    else:
        attn_out, kl, cache = attention_full(
            p["attn"], h, cfg, rope_positions=rope_positions,
            segment_ids=segment_ids, distill=distill,
            collect_cache=collect_cache, collect_gate=collect_gate)
    x = x + attn_out
    h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "moe" in p:
        b, l, d = h2.shape
        y, aux = moe_mod.moe_mlp(p["moe"], h2.reshape(b * l, d), cfg.moe,
                                 cfg.activation, shard)
        y = y.reshape(b, l, d)
    else:
        y = mlp(p["mlp"], h2, cfg.activation)
    return x + y, kl, aux, cache


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    policies = {
        "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
        "full": jax.checkpoint_policies.everything_saveable,
    }
    return jax.checkpoint(fn, policy=policies[cfg.remat])


def lm_backbone(params: Params, x: jnp.ndarray, cfg: ModelConfig, *,
                rope_positions, segment_ids, distill: bool,
                cross_ctx=None, collect_cache: bool = False,
                collect_gate: bool = False, shard=None):
    """Runs the layer stack. Returns (x, kl_sum, aux_sum, caches|None)."""

    def self_body(carry, layer_p):
        x, kl, aux = carry
        y, l_kl, l_aux, cache = block_fwd_full(
            layer_p, x, cfg, rope_positions=rope_positions,
            segment_ids=segment_ids, distill=distill,
            collect_cache=collect_cache, collect_gate=collect_gate,
            shard=shard)
        return (y, kl + l_kl, aux + l_aux), cache

    self_body = _remat(self_body, cfg)
    zero = jnp.zeros((), jnp.float32)

    if cfg.cross_attn_period:
        def unit_body(carry, unit_p):
            (x, kl, aux) = carry
            (x, kl, aux), caches = layer_scan(
                self_body, (x, kl, aux), unit_p["self"],
                unroll=not cfg.scan_layers)
            x2, c_kl, c_aux, _ = block_fwd_full(
                unit_p["cross"], x, cfg, rope_positions=rope_positions,
                segment_ids=segment_ids, distill=False, cross_ctx=cross_ctx,
                is_cross=True, shard=shard)
            return (x2, kl + c_kl, aux + c_aux), caches

        units = {"self": params["blocks"], "cross": params["cross_blocks"]}
        (x, kl, aux), caches = layer_scan(unit_body, (x, zero, zero), units,
                                          unroll=not cfg.scan_layers)
        if collect_cache and caches is not None:
            # [n_units, n_self, ...] -> [n_layers_self, ...]
            caches = jax.tree.map(
                lambda c: c.reshape((-1,) + c.shape[2:]), caches)
        return x, kl, aux, caches

    (x, kl, aux), caches = layer_scan(self_body, (x, zero, zero),
                                      params["blocks"],
                                      unroll=not cfg.scan_layers)
    return x, kl, aux, caches


def lm_forward(params: Params, batch: Dict[str, jnp.ndarray],
               cfg: ModelConfig, *, mode: str = "pretrain", shard=None):
    """mode: 'pretrain' -> (loss, metrics); 'distill' -> (kl_loss, metrics)."""
    if cfg.family == "audio":
        x = linear(params["in_proj"], batch["features"])
    else:
        x = jnp.take(params["embed"]["w"], batch["tokens"], axis=0)
    b, l = x.shape[:2]
    pos = batch.get("positions")
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))
    seg = batch.get("segment_ids")
    cross_ctx = batch.get("image_embeds")

    x, kl, aux, _ = lm_backbone(params, x, cfg, rope_positions=pos,
                                segment_ids=seg, distill=(mode == "distill"),
                                cross_ctx=cross_ctx, shard=shard)
    if mode == "distill":
        n_gate_layers = _n_gate_layers(cfg)
        kl = kl / max(n_gate_layers, 1)
        return kl + aux * 0.0, {"kl": kl}
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["w"].T
    else:
        logits = linear(params["lm_head"], x)
    loss = cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))
    return loss + aux, {"ce": loss, "aux": aux}


def _n_gate_layers(cfg: ModelConfig) -> int:
    if not (cfg.gate.enabled and cfg.has_attention and cfg.is_decoder):
        return 0
    if cfg.cross_attn_period:
        n_units = cfg.num_layers // cfg.cross_attn_period
        return n_units * (cfg.cross_attn_period - 1)
    return cfg.num_layers


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV cache and K-compression cache
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    """All caches are HEAD-MAJOR (ISSUE 2 invariant: the decode hot path
    never transposes or copies a cache-sized array — prefill does the one
    layout conversion, decode reads/writes the native layout).

    ``meta_*`` is the incremental selection-metadata cache
    (core.metacache): per-block key min/max for metadata-reading policies
    (QuestPolicy). Built at prefill only when the prefill ``options``
    carry such a policy (None otherwise) and advanced per step only for
    the policy that reads it — the same rule as the Kg cache."""
    k_cache: jnp.ndarray          # [L, B, Hkv, S_max, Dh]  (post-rope)
    v_cache: jnp.ndarray          # [L, B, Hkv, S_max, Dh]
    kg_cache: Optional[jnp.ndarray]     # [L, B, Hkv, nb_max, Dg]
    kg_n: Optional[jnp.ndarray]         # [L, B]
    cur_len: jnp.ndarray          # [B]
    cross_k: Optional[jnp.ndarray] = None   # [Lc, B, Hkv, n_img, Dh]
    cross_v: Optional[jnp.ndarray] = None
    meta_kmin: Optional[jnp.ndarray] = None  # [L, B, Hkv, nb_max, Dh] f32
    meta_kmax: Optional[jnp.ndarray] = None  # [L, B, Hkv, nb_max, Dh] f32
    meta_n: Optional[jnp.ndarray] = None     # [L, B] int32


def n_self_layers(cfg: ModelConfig) -> int:
    if cfg.cross_attn_period:
        return (cfg.num_layers // cfg.cross_attn_period) * (cfg.cross_attn_period - 1)
    return cfg.num_layers


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      dtype=None,
                      options: Optional[DecodeOptions] = None) -> DecodeState:
    dt = dtype or jnp.dtype(cfg.dtype)
    dh, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    nl = n_self_layers(cfg)
    nb_max = max_len // cfg.gate.block_size
    gate_on = cfg.gate.enabled
    kg = (jnp.zeros((nl, batch, hkv, nb_max, cfg.gate.d_gate), dt)
          if gate_on else None)
    kg_n = jnp.zeros((nl, batch), jnp.int32) if gate_on else None
    meta_kmin = meta_kmax = meta_n = None
    if options is not None and options.policy.needs_meta:
        meta_kmin = jnp.zeros((nl, batch, hkv, nb_max, dh), jnp.float32)
        meta_kmax = jnp.zeros((nl, batch, hkv, nb_max, dh), jnp.float32)
        meta_n = jnp.zeros((nl, batch), jnp.int32)
    cross = None
    if cfg.cross_attn_period:
        n_units = cfg.num_layers // cfg.cross_attn_period
        cross = jnp.zeros((n_units, batch, hkv, cfg.n_image_tokens, dh), dt)
    return DecodeState(
        k_cache=jnp.zeros((nl, batch, hkv, max_len, dh), dt),
        v_cache=jnp.zeros((nl, batch, hkv, max_len, dh), dt),
        kg_cache=kg, kg_n=kg_n,
        cur_len=jnp.zeros((batch,), jnp.int32),
        cross_k=cross, cross_v=cross,
        meta_kmin=meta_kmin, meta_kmax=meta_kmax, meta_n=meta_n)


def attention_decode(p: Params, x1: jnp.ndarray, cfg: ModelConfig, *,
                     k_cache, v_cache, kg_cache, kg_n, cur_len,
                     options: DecodeOptions, meta_kmin=None, meta_kmax=None,
                     meta_n=None, shard=None, stage=None, plan=None):
    """One token. x1 [B,1,d]; caches for ONE layer HEAD-MAJOR [B,Hkv,S,Dh].
    Returns (out, new_layer_state, selection_aux) — or, when ``stage`` is
    given, (out, new_layer_state, selection_aux, plan_out).

    ``options.policy`` picks the block-selection strategy (core.policy);
    ``options.kernel_impl='sharded'`` takes the sequence-parallel
    shard_map path (repro.serve.sharded): explicit split-K collectives
    instead of GSPMD resharding of the gathered cache — requires a mesh
    on ``shard`` and the gate policy (distributed gate top-k).

    ``stage``/``plan`` (step-level SelectionSchedule, plan-carrying
    schedules only): ``stage`` is this layer's staging id (a traced int32
    scalar from the jit-static schedule array — STAGE_DENSE runs dense
    attention, STAGE_SELECT computes a fresh selection, STAGE_REUSE
    attends the carried ``plan`` [B, Hkv, k] as-is) and the returned
    ``plan_out`` is the plan for the NEXT layer. The Kg / selection-
    metadata caches advance only at selecting layers ("advance only for
    the reader": a selecting layer advances every step so its view is
    always current; dense/reuse layers never read theirs).
    """
    b = x1.shape[0]
    dh, hkv, g = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.gqa_group
    bs = cfg.gate.block_size
    policy = options.policy
    sparse_on = _policy_active(policy, p)
    q, k, v = _qkv(p, x1, cfg)
    q_nope = q
    pos = cur_len[:, None]                                 # [B,1]
    qr = apply_rope(q, pos, cfg.rope)
    kr = apply_rope(k, pos, cfg.rope)

    mesh = getattr(shard, "mesh", None)
    if sparse_on and options.kernel_impl == "sharded" and policy.needs_gate \
            and "gate" in p and mesh is not None:
        from repro.distributed.sharding import decode_partition
        from repro.serve.sharded import sharded_sparse_decode
        bspec, seq_axes = decode_partition(mesh, b)
        qg = ag.gate_q(p["gate"], q_nope, pos, cfg.gate)[:, 0]  # [B,Hkv,Dg]
        qgrp = qr[:, 0].reshape(b, hkv, g, dh)
        o, k_cache, v_cache, kg_cache, n_sel = sharded_sparse_decode(
            qg, qgrp, kr[:, 0], v[:, 0], k_cache, v_cache, kg_cache,
            cur_len, p["gate"]["wk"], mesh=mesh, seq_axes=seq_axes,
            batch_spec=bspec, cfg=cfg.gate, rope=cfg.rope,
            max_selected=options.max_selected(cfg))
        new_len = cur_len + 1
        completed = (new_len % bs) == 0
        kg_n = jnp.where(completed, new_len // bs, kg_n).astype(jnp.int32)
        o = o.reshape(b, 1, hkv * g, dh)
        out = linear(p["wo"], o.reshape(b, 1, hkv * g * dh))
        if options.measure_sparsity:
            # measured sparsity from the shards' psum'd selection counts
            n_valid = kc.visible_blocks(jnp.maximum(new_len, 1), bs)
            frac = n_sel.astype(jnp.float32) \
                / jnp.maximum(n_valid[:, None].astype(jnp.float32), 1.0)
            rho_rows = 1.0 - jnp.mean(frac, axis=1)
            aux = (jnp.mean(rho_rows), rho_rows,
                   jnp.mean(n_sel.astype(jnp.float32), axis=1),
                   n_valid.astype(jnp.float32))
        else:
            aux = _zero_layer_aux(b)
        return out, (k_cache, v_cache, kg_cache, kg_n,
                     meta_kmin, meta_kmax, meta_n), aux

    if sparse_on and options.kernel_impl == "sharded":
        # only reachable by bypassing DecodeOptions validation (non-gate
        # policy, ungated layer, or no mesh on ``shard``): fail at trace
        # time with guidance instead of a bare ValueError('sharded') from
        # the kernel dispatch (mirrors the paged path's check)
        raise ValueError(
            "kernel_impl='sharded' on the contiguous path needs a "
            "mesh-aware engine (shard=make_shard_fn(mesh)) and GatePolicy "
            "on a gated layer; other policies run with kernel_impl="
            "'ref'/'pallas'")
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, :, cur_len].set(kr[:, 0])
    v_cache = v_cache.at[bidx, :, cur_len].set(v[:, 0])
    new_len = cur_len + 1

    if stage is not None and sparse_on:
        # ---- staged path (plan-carrying SelectionSchedule) ------------
        do_select = stage == STAGE_SELECT             # traced bool scalar
        is_dense = stage == STAGE_DENSE

        if policy.needs_gate and "gate" in p and kg_cache is not None:
            def _adv_kg(kg, n):
                cache = kc.update_kcache(
                    kc.KCompressionCache(kg, n), p["gate"], k_cache,
                    new_len, cfg.gate, cache_is_roped=True,
                    rope=cfg.rope)
                return cache.kg, cache.n_complete
            kg_cache, kg_n = jax.lax.cond(
                do_select, _adv_kg, lambda kg, n: (kg, n), kg_cache, kg_n)
        if policy.needs_meta and meta_kmin is not None:
            def _adv_meta(mn, mx, n):
                return tuple(mc.update_metacache(
                    mc.SelectionMetaCache(mn, mx, n), k_cache, new_len, bs))
            meta_kmin, meta_kmax, meta_n = jax.lax.cond(
                do_select, _adv_meta, lambda mn, mx, n: (mn, mx, n),
                meta_kmin, meta_kmax, meta_n)

        inp = SelectionInputs(q_nope=q_nope, qr=qr, pos=pos, new_len=new_len,
                              gate_params=p.get("gate"), kg=kg_cache,
                              k_cache=k_cache, meta_kmin=meta_kmin,
                              meta_kmax=meta_kmax)

        def _fresh(cur):
            del cur
            return policy.select(
                inp, cfg, impl=select_impl(options.impl),
                max_selected=options.max_selected(cfg),
                unify_heads=options.schedule.unify_heads).astype(jnp.int32)

        idx = jax.lax.cond(do_select, _fresh, lambda cur: cur, plan)
        qgrp = qr[:, 0].reshape(b, hkv, g, dh)

        def _run_sparse(_):
            o = ops.sparse_decode(qgrp, k_cache, v_cache, idx, new_len,
                                  block_size=bs, impl=options.impl)
            return o.reshape(b, 1, hkv * g, dh)

        def _run_dense(_):
            return decode_attention(
                qr, k_cache, v_cache, new_len,
                logit_softcap=cfg.attn_logit_softcap).reshape(
                    b, 1, hkv * g, dh)

        o = jax.lax.cond(is_dense, _run_dense, _run_sparse, None)
        if options.measure_sparsity:
            sel = _selection_aux(idx, kc.visible_blocks(
                jnp.maximum(new_len, 1), bs), k_cache.shape[2] // bs)
            den = _dense_aux(new_len, bs)
            aux = tuple(jnp.where(is_dense, d, s) for s, d in zip(sel, den))
        else:
            aux = _zero_layer_aux(b)
        out = linear(p["wo"], o.reshape(b, 1, hkv * g * dh))
        return out, (k_cache, v_cache, kg_cache, kg_n,
                     meta_kmin, meta_kmax, meta_n), aux, idx

    if sparse_on:
        # the Kg cache only advances for the policy that reads it — a
        # quest/oracle/sliding rollout skips the per-step gate-K
        # projection entirely (each engine's options are fixed, so no
        # consumer can appear mid-run)
        if policy.needs_gate and "gate" in p and kg_cache is not None:
            cache = kc.update_kcache(
                kc.KCompressionCache(kg_cache, kg_n), p["gate"], k_cache,
                new_len, cfg.gate, cache_is_roped=True,
                rope=cfg.rope)
            kg_cache, kg_n = cache.kg, cache.n_complete
        # same advance-only-for-the-reader rule for the selection-metadata
        # cache (QuestPolicy): O(block_size) finalize on block boundaries
        if policy.needs_meta and meta_kmin is not None:
            mcache = mc.update_metacache(
                mc.SelectionMetaCache(meta_kmin, meta_kmax, meta_n),
                k_cache, new_len, bs)
            meta_kmin, meta_kmax, meta_n = mcache
        inp = SelectionInputs(q_nope=q_nope, qr=qr, pos=pos, new_len=new_len,
                              gate_params=p.get("gate"), kg=kg_cache,
                              k_cache=k_cache, meta_kmin=meta_kmin,
                              meta_kmax=meta_kmax)
        idx = policy.select(inp, cfg, impl=select_impl(options.impl),
                            max_selected=options.max_selected(cfg),
                            unify_heads=options.schedule.unify_heads)
        qgrp = qr[:, 0].reshape(b, hkv, g, dh)
        o = ops.sparse_decode(qgrp, k_cache, v_cache, idx, new_len,
                              block_size=bs, impl=options.impl)
        o = o.reshape(b, 1, hkv * g, dh)
        aux = (_selection_aux(idx, kc.visible_blocks(
                   jnp.maximum(new_len, 1), bs), k_cache.shape[2] // bs)
               if options.measure_sparsity else _zero_layer_aux(b))
    else:
        o = decode_attention(qr, k_cache, v_cache, new_len,
                             logit_softcap=cfg.attn_logit_softcap)
        aux = (_dense_aux(new_len, bs) if options.measure_sparsity
               else _zero_layer_aux(b))
    out = linear(p["wo"], o.reshape(b, 1, hkv * g * dh))
    ret = (out, (k_cache, v_cache, kg_cache, kg_n,
                 meta_kmin, meta_kmax, meta_n), aux)
    # an ungated layer under a plan-carrying schedule (needs_gate policy
    # without a gate): dense fallback, the plan passes through untouched
    return ret + (plan,) if stage is not None else ret


def block_decode(p: Params, x1, cfg: ModelConfig, layer_state, cur_len, *,
                 options: DecodeOptions, shard=None, stage=None, plan=None):
    k_cache, v_cache, kg_cache, kg_n, meta_kmin, meta_kmax, meta_n = \
        layer_state
    h = rms_norm(p["ln1"], x1, cfg.norm_eps)
    ret = attention_decode(
        p["attn"], h, cfg, k_cache=k_cache, v_cache=v_cache,
        kg_cache=kg_cache, kg_n=kg_n, cur_len=cur_len, options=options,
        meta_kmin=meta_kmin, meta_kmax=meta_kmax, meta_n=meta_n,
        shard=shard, stage=stage, plan=plan)
    attn_out, new_state, aux = ret[:3]
    x1 = x1 + attn_out
    h2 = rms_norm(p["ln2"], x1, cfg.norm_eps)
    if "moe" in p:
        b = x1.shape[0]
        y, _ = moe_mod.moe_mlp(p["moe"], h2.reshape(b, -1), cfg.moe,
                               cfg.activation, shard)
        y = y.reshape(b, 1, -1)
    else:
        y = mlp(p["mlp"], h2, cfg.activation)
    if stage is not None:
        return x1 + y, new_state, aux, ret[3]
    return x1 + y, new_state, aux


def cross_block_decode(p: Params, x1, cfg: ModelConfig, ck, cv):
    """Cross-attn block at decode: context K/V precomputed at prefill."""
    b = x1.shape[0]
    dh, hkv, g = cfg.resolved_head_dim, cfg.n_kv_heads, cfg.gqa_group
    h = rms_norm(p["ln1"], x1, cfg.norm_eps)
    q = linear(p["attn"]["wq"], h).reshape(b, 1, cfg.n_heads, dh)
    if cfg.qk_norm:
        q = rms_norm(p["attn"]["q_norm"], q, cfg.norm_eps)
    n_img = ck.shape[2]                  # ck head-major [B, Hkv, n_img, Dh]
    o = decode_attention(q, ck, cv, jnp.full((b,), n_img, jnp.int32))
    x1 = x1 + linear(p["attn"]["wo"], o.reshape(b, 1, -1))
    h2 = rms_norm(p["ln2"], x1, cfg.norm_eps)
    return x1 + mlp(p["mlp"], h2, cfg.activation)


def lm_decode_step(params: Params, state: DecodeState, token: jnp.ndarray,
                   cfg: ModelConfig, *,
                   options: Optional[DecodeOptions] = None, shard=None):
    """token [B] -> (logits [B, V], new DecodeState, aux dict).

    ``options`` (static) selects policy/kernel/budget — see
    ``core.policy.DecodeOptions``; None means the config default
    (GatePolicy when the config carries a gate). ``aux`` reports the
    MEASURED selection of this step (sparsity/sel_blocks/vis_blocks),
    averaged over layers.
    """
    options = options if options is not None else default_options(cfg)
    x1 = jnp.take(params["embed"]["w"], token[:, None], axis=0)

    def self_scan(carry, inp):
        x1 = carry
        layer_p, layer_state = inp
        y, new_state, aux = block_decode(layer_p, x1, cfg, layer_state,
                                         state.cur_len, options=options,
                                         shard=shard)
        return y, (new_state, aux)

    layer_states = (state.k_cache, state.v_cache, state.kg_cache, state.kg_n,
                    state.meta_kmin, state.meta_kmax, state.meta_n)

    if options.schedule.needs_plan:
        # ---- step-level selection plan (SelectionSchedule) ------------
        # staging is jit-static: the schedule becomes a [n_layers] int32
        # array scanned alongside the layer params, the plan a carried
        # [B, Hkv, k] index list reused/refreshed per the stage ids.
        if cfg.cross_attn_period:
            raise NotImplementedError(
                "SelectionSchedule plans assume a uniform self-attn stack; "
                "cross-attn unit families keep per-layer selection "
                "(schedule=SelectionSchedule())")
        if options.kernel_impl == "sharded":
            raise NotImplementedError(
                "the contiguous sharded path fuses selection into the "
                "shard_map body (sharded_sparse_decode) and cannot carry a "
                "plan; plan-carrying schedules run with kernel_impl="
                "'ref'/'pallas', or use the paged sharded path")
        stages = jnp.asarray(
            options.schedule.layer_stages(n_self_layers(cfg)), jnp.int32)
        nb = state.k_cache.shape[3] // cfg.gate.block_size
        width = selection_width(options.policy, cfg, nb,
                                options.max_selected(cfg))
        plan0 = jnp.full((token.shape[0], cfg.n_kv_heads, width), -1,
                         jnp.int32)

        def plan_scan(carry, inp):
            x1, plan = carry
            layer_p, layer_state, stage = inp
            y, new_state, aux, plan = block_decode(
                layer_p, x1, cfg, layer_state, state.cur_len,
                options=options, shard=shard, stage=stage, plan=plan)
            return (y, plan), (new_state, aux)

        (x1, _), (new_states, auxs) = layer_scan(
            plan_scan, (x1, plan0), (params["blocks"], layer_states, stages),
            unroll=not cfg.scan_layers)
    elif cfg.cross_attn_period:
        n_units = cfg.num_layers // cfg.cross_attn_period
        n_self = cfg.cross_attn_period - 1

        def unit_scan(x1, inp):
            unit_p, unit_states, cross_p, ck, cv = inp
            x1, ys = layer_scan(self_scan, x1, (unit_p, unit_states),
                                unroll=not cfg.scan_layers)
            x1 = cross_block_decode(cross_p, x1, cfg, ck, cv)
            return x1, ys

        shaped = jax.tree.map(
            lambda c: c.reshape((n_units, n_self) + c.shape[1:]) if c is not None else None,
            layer_states)
        x1, (new_states, auxs) = layer_scan(
            unit_scan, x1,
            (params["blocks"], shaped, params["cross_blocks"],
             state.cross_k, state.cross_v), unroll=not cfg.scan_layers)
        new_states = jax.tree.map(
            lambda c: c.reshape((-1,) + c.shape[2:]) if c is not None else None,
            new_states)
        auxs = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), auxs)
    else:
        x1, (new_states, auxs) = layer_scan(self_scan, x1,
                                            (params["blocks"], layer_states),
                                            unroll=not cfg.scan_layers)

    x1 = rms_norm(params["final_norm"], x1, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = x1 @ params["embed"]["w"].T
    else:
        logits = linear(params["lm_head"], x1)
    new_state = DecodeState(
        k_cache=new_states[0], v_cache=new_states[1],
        kg_cache=new_states[2], kg_n=new_states[3],
        cur_len=state.cur_len + 1,
        cross_k=state.cross_k, cross_v=state.cross_v,
        meta_kmin=new_states[4], meta_kmax=new_states[5],
        meta_n=new_states[6])
    return logits[:, 0], new_state, aggregate_decode_aux(auxs)


# ---------------------------------------------------------------------------
# paged decode (continuous batching): per-row ragged lengths + page pools
# ---------------------------------------------------------------------------

def lm_decode_step_paged(params: Params, pages, slot_state,
                         token: jnp.ndarray, page_table: jnp.ndarray,
                         cur_len: jnp.ndarray, active: jnp.ndarray,
                         cfg: ModelConfig, *,
                         options: Optional[DecodeOptions] = None,
                         budget_blocks=None, shard=None):
    """Continuous-batching decode step. token/cur_len/active [n_slots];
    pages is a ``serve.paging.PagedPages`` (layer-stacked pools, carried
    through the layer scan whole and written in place at each layer's
    index);
    page_table [n_slots, npt]; ``budget_blocks`` [n_slots] (optional,
    runtime) per-slot selected-block caps for per-request budget
    overrides. Returns (logits [n_slots, V], new pages, slot_state, aux
    dict).

    ``slot_state`` is the unified per-slot RECURRENT-state seam (PR 10):
    families with recurrent layers (ssm/hybrid) carry a
    ``serve.slotstate.SlotState`` through every step; the transformer is
    pages-only, so it takes and returns ``None`` (an empty pytree — jit
    treats it as zero operands, and the engine threads it without
    special-casing the family).

    Inactive rows produce garbage logits (the engine masks them) but do
    not touch live pages or advance — per-row raggedness is carried by
    ``cur_len``/``active`` rather than a uniform batch length. A
    mesh-aware ``shard`` plus ``options.kernel_impl='sharded'`` runs the
    paged x sharded path (pools head-sharded, see
    ``attention_decode_paged``)."""
    if cfg.cross_attn_period:
        raise NotImplementedError("paged decode: cross-attn families TBD")
    options = options if options is not None else default_options(cfg)
    x1 = jnp.take(params["embed"]["w"], token[:, None], axis=0)
    # the pools ride the scan CARRY and each layer writes them in place at
    # its index; as xs/ys they would be sliced per layer and restacked
    # into a fresh pool every step (a scan cannot alias xs with ys)
    layers = jnp.arange(pages.k_pages.shape[0], dtype=jnp.int32)

    if options.schedule.needs_plan:
        # step-level selection plan: same staging as lm_decode_step, the
        # carried plan sized [n_slots, Hkv, k] against the page table's
        # logical-block count
        stages = jnp.asarray(
            options.schedule.layer_stages(n_self_layers(cfg)), jnp.int32)
        width = selection_width(options.policy, cfg, page_table.shape[1],
                                options.max_selected(cfg))
        plan0 = jnp.full((token.shape[0], cfg.n_kv_heads, width), -1,
                         jnp.int32)

        def plan_scan(carry, inp):
            x1, plan, pages = carry
            layer_p, layer, stage = inp
            y, pages, aux, plan = block_decode_paged(
                layer_p, x1, cfg, pages, layer, page_table, cur_len, active,
                options=options, budget_blocks=budget_blocks, shard=shard,
                stage=stage, plan=plan)
            return (y, plan, pages), aux

        (x1, _, pages), auxs = layer_scan(
            plan_scan, (x1, plan0, pages), (params["blocks"], layers, stages),
            unroll=not cfg.scan_layers)
    else:
        def self_scan(carry, inp):
            x1, pages = carry
            layer_p, layer = inp
            y, pages, aux = block_decode_paged(
                layer_p, x1, cfg, pages, layer, page_table, cur_len, active,
                options=options, budget_blocks=budget_blocks, shard=shard)
            return (y, pages), aux

        (x1, pages), auxs = layer_scan(self_scan, (x1, pages),
                                       (params["blocks"], layers),
                                       unroll=not cfg.scan_layers)
    with jax.named_scope("lm_head"):
        x1 = rms_norm(params["final_norm"], x1, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x1 @ params["embed"]["w"].T
        else:
            logits = linear(params["lm_head"], x1)
    return logits[:, 0], pages, slot_state, aggregate_decode_aux(auxs)


def lm_prefill(params: Params, batch: Dict[str, jnp.ndarray],
               cfg: ModelConfig, max_len: int, shard=None,
               options: Optional[DecodeOptions] = None
               ) -> Tuple[jnp.ndarray, DecodeState]:
    """Full forward filling the caches. Returns (last logits, state).

    ``batch["lengths"]`` (optional, [B] int): TRUE per-row prompt lengths
    when ``tokens`` is right-padded to a bucketed width (the serve-path
    prefill bucketing, ISSUE 5 satellite). Causality keeps real positions
    unaffected by the pad tokens; the returned logits are gathered at
    ``lengths - 1``, ``cur_len``/``kg_n`` reflect the true lengths, and
    Kg rows whose block contains any pad token are zeroed (the staleness
    contract: a partial trailing block reads a ZERO row).

    ``options`` (the same DecodeOptions the decode steps will run with)
    additionally builds the selection-metadata cache (core.metacache)
    when its policy reads one — the bulk O(S) pass that makes every
    subsequent QuestPolicy step O(block_size)."""
    tokens = batch["tokens"]
    b, l = tokens.shape
    lengths = batch.get("lengths")                       # [B] | None
    x = jnp.take(params["embed"]["w"], tokens, axis=0)
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))
    cross_ctx = batch.get("image_embeds")

    x, _, _, caches = lm_backbone(params, x, cfg, rope_positions=pos,
                                  segment_ids=None, distill=False,
                                  cross_ctx=cross_ctx, collect_cache=True,
                                  shard=shard)
    kr, v, kg = caches                       # [L, B, S, Hkv, Dh] stacked
    nl = kr.shape[0]
    pad = max_len - l
    # the ONE-TIME layout conversion: prefill activations are seq-major,
    # the decode caches are head-major [L, B, Hkv, S, Dh] (ISSUE 2: no
    # cache-sized transpose ever happens after this point)
    k_cache = jnp.pad(jnp.moveaxis(kr, 3, 2),
                      ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    v_cache = jnp.pad(jnp.moveaxis(v, 3, 2),
                      ((0, 0), (0, 0), (0, 0), (0, pad), (0, 0)))
    cur_len = (jnp.full((b,), l, jnp.int32) if lengths is None
               else lengths.astype(jnp.int32))
    kg_cache = kg_n = None
    if kg is not None:
        nb_max = max_len // cfg.gate.block_size
        nb = kg.shape[2]
        kg_cache = jnp.pad(jnp.moveaxis(kg, 3, 2),
                           ((0, 0), (0, 0), (0, 0), (0, nb_max - nb),
                            (0, 0))).astype(jnp.dtype(cfg.dtype))
        kg_n = jnp.broadcast_to(cur_len // cfg.gate.block_size,
                                (nl, b)).astype(jnp.int32)
        if lengths is not None:
            # bucketed prefill: blocks touching pad tokens hold garbage Kg
            # rows — zero them (rows >= lengths // bs), keeping the
            # partial-trailing-block-reads-zero staleness contract
            row_ok = (jnp.arange(nb_max)[None, :]
                      < (cur_len // cfg.gate.block_size)[:, None])
            kg_cache = jnp.where(row_ok[None, :, None, :, None], kg_cache,
                                 jnp.zeros((), kg_cache.dtype))

    meta_kmin = meta_kmax = meta_n = None
    if options is not None and options.policy.needs_meta:
        # bulk-build the selection-metadata cache off the head-major K
        # cache (the one allowed O(S) pass; kv_len masking keeps pad /
        # beyond-length tokens out of the min/max)
        def one_layer(kc_1l):
            return mc.prefill_metacache(
                mc.init_metacache(b, max_len // cfg.gate.block_size,
                                  cfg.n_kv_heads, cfg.resolved_head_dim),
                kc_1l, cur_len, cfg.gate.block_size)
        meta_kmin, meta_kmax, meta_n = jax.vmap(one_layer)(k_cache)

    cross_k = cross_v = None
    if cfg.cross_attn_period and cross_ctx is not None:
        def cross_kv(cp):
            dh = cfg.resolved_head_dim
            ck = linear(cp["attn"]["wk"], cross_ctx).reshape(
                b, -1, cfg.n_kv_heads, dh)
            cv = linear(cp["attn"]["wv"], cross_ctx).reshape(
                b, -1, cfg.n_kv_heads, dh)
            if cfg.qk_norm:
                ck = rms_norm(cp["attn"]["k_norm"], ck, cfg.norm_eps)
            # head-major, matching decode_attention's native layout
            return jnp.swapaxes(ck, 1, 2), jnp.swapaxes(cv, 1, 2)
        cross_k, cross_v = jax.vmap(cross_kv)(params["cross_blocks"])

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    last = (x[:, -1] if lengths is None
            else x[jnp.arange(b), jnp.maximum(cur_len - 1, 0)])
    if cfg.tie_embeddings:
        logits = last @ params["embed"]["w"].T
    else:
        logits = linear(params["lm_head"], last)
    state = DecodeState(k_cache=k_cache, v_cache=v_cache, kg_cache=kg_cache,
                        kg_n=kg_n, cur_len=cur_len,
                        cross_k=cross_k, cross_v=cross_v,
                        meta_kmin=meta_kmin, meta_kmax=meta_kmax,
                        meta_n=meta_n)
    return logits, state


def lm_gate_collect(params: Params, batch: Dict[str, jnp.ndarray],
                    cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    """Gate-quality evaluation pass (benchmark harness).

    Runs the full-sequence forward in distill mode collecting, per layer:
      glog [L, B, Hkv, Lq, nb]  masked gate logits
      gt   [L, B, Hkv, Lq, nb]  distillation ground truth (block-mass dist.)
      qr/kr [L, B, Lq, H(kv), Dh] post-rope Q/K (for the Quest baseline).
    Only meaningful for gated attention families at REDUCED scale.
    """
    x = jnp.take(params["embed"]["w"], batch["tokens"], axis=0)
    b, l = x.shape[:2]
    pos = batch.get("positions")
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))
    _, _, _, extras = lm_backbone(
        params, x, cfg, rope_positions=pos,
        segment_ids=batch.get("segment_ids"), distill=True,
        cross_ctx=batch.get("image_embeds"), collect_gate=True)
    return extras
