"""Zamba2-style hybrid: Mamba2 backbone + ONE weight-shared attention block
invoked every ``hybrid_period`` SSM layers (each invocation has its own KV
cache). The shared attention block carries a SeerAttention-R gate — the
paper's technique applies exactly there (DESIGN.md §5).

Layer plan for num_layers=38, period=6:
  6 units x (6 mamba2 + shared-attn) + 2 trailing mamba2 layers.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import mamba
from repro.models import transformer as tf
from repro.models.common import (cross_entropy_loss, init_linear,
                                 init_rmsnorm, layer_scan, linear, rms_norm)

Params = Dict[str, Any]


def _plan(cfg: ModelConfig) -> Tuple[int, int, int]:
    period = cfg.hybrid_period
    n_units = cfg.num_layers // period
    rem = cfg.num_layers - n_units * period
    return n_units, period, rem


class HybridDecodeState(NamedTuple):
    conv: jnp.ndarray          # [L_m, B, K-1, di+2n]
    h: jnp.ndarray             # [L_m, B, nh, hd, n]
    k_cache: jnp.ndarray       # [n_units, B, Hkv, S, Dh]  (head-major)
    v_cache: jnp.ndarray
    kg_cache: Optional[jnp.ndarray]   # [n_units, B, Hkv, nb, Dg]
    kg_n: Optional[jnp.ndarray]
    cur_len: jnp.ndarray


def _init_mblock(key, cfg: ModelConfig) -> Params:
    return {"ln": init_rmsnorm(cfg.d_model, cfg.dtype),
            "mixer": mamba.init_mamba2(key, cfg)}


def init_lm(key, cfg: ModelConfig) -> Params:
    n_units, period, rem = _plan(cfg)
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p: Params = {
        "embed": {"w": (jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                          jnp.float32) * 0.02).astype(dt)},
        "units": jax.vmap(lambda k: jax.vmap(
            lambda kk: _init_mblock(kk, cfg))(jax.random.split(k, period)))(
            jax.random.split(ks[1], n_units)),
        "shared_attn": tf.init_block(ks[2], cfg,
                                     with_gate=cfg.gate.enabled),
        "final_norm": init_rmsnorm(cfg.d_model, cfg.dtype),
    }
    if rem:
        p["tail"] = jax.vmap(lambda k: _init_mblock(k, cfg))(
            jax.random.split(ks[3], rem))
    if not cfg.tie_embeddings:
        p["lm_head"] = init_linear(ks[4], cfg.d_model, cfg.vocab_size, cfg.dtype)
    return p


def _remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def _mamba_scan(x, blocks, cfg, collect_state=False, lengths=None):
    def body(x, bp):
        y, st = mamba.mamba2_full(bp["mixer"],
                                  rms_norm(bp["ln"], x, cfg.norm_eps), cfg,
                                  lengths=lengths)
        return x + y, (st if collect_state else None)
    return layer_scan(_remat(body, cfg), x, blocks,
                      unroll=not cfg.scan_layers)


def lm_forward(params: Params, batch, cfg: ModelConfig, *, mode="pretrain",
               shard=None):
    n_units, period, rem = _plan(cfg)
    tokens = batch["tokens"]
    b, l = tokens.shape
    x = jnp.take(params["embed"]["w"], tokens, axis=0)
    pos = batch.get("positions")
    if pos is None:
        pos = jnp.broadcast_to(jnp.arange(l), (b, l))
    seg = batch.get("segment_ids")
    distill = mode == "distill"
    zero = jnp.zeros((), jnp.float32)

    def unit(carry, unit_blocks):
        x, kl = carry
        x, _ = _mamba_scan(x, unit_blocks, cfg)
        x, l_kl, _, _ = tf.block_fwd_full(
            params["shared_attn"], x, cfg, rope_positions=pos,
            segment_ids=seg, distill=distill, shard=shard)
        return (x, kl + l_kl), None

    (x, kl), _ = layer_scan(unit, (x, zero), params["units"],
                            unroll=not cfg.scan_layers)
    if rem:
        x, _ = _mamba_scan(x, params["tail"], cfg)
    if distill:
        kl = kl / max(n_units, 1)
        return kl, {"kl": kl}
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = (x @ params["embed"]["w"].T if cfg.tie_embeddings
              else linear(params["lm_head"], x))
    loss = cross_entropy_loss(logits, batch["labels"], batch.get("loss_mask"))
    return loss, {"ce": loss}


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int
                      ) -> HybridDecodeState:
    n_units, period, rem = _plan(cfg)
    di, hd, nh, n = mamba._m2_dims(cfg)
    dt = jnp.dtype(cfg.dtype)
    lm = n_units * period + rem
    dh, hkv = cfg.resolved_head_dim, cfg.n_kv_heads
    nb_max = max_len // cfg.gate.block_size
    gate_on = cfg.gate.enabled
    return HybridDecodeState(
        conv=jnp.zeros((lm, batch, cfg.ssm.conv_dim - 1, di + 2 * n), dt),
        h=jnp.zeros((lm, batch, nh, hd, n), jnp.float32),
        k_cache=jnp.zeros((n_units, batch, hkv, max_len, dh), dt),
        v_cache=jnp.zeros((n_units, batch, hkv, max_len, dh), dt),
        kg_cache=(jnp.zeros((n_units, batch, hkv, nb_max, cfg.gate.d_gate), dt)
                  if gate_on else None),
        kg_n=(jnp.zeros((n_units, batch), jnp.int32) if gate_on else None),
        cur_len=jnp.zeros((batch,), jnp.int32))


def lm_prefill(params: Params, batch, cfg: ModelConfig, max_len: int,
               shard=None, options=None):
    """``options`` accepted for ModelApi uniformity; the hybrid family has
    no selection-metadata cache (QuestPolicy raises with guidance).

    ``batch["lengths"]`` [B] (optional): true per-row lengths for bucketed
    right-padded prompts (PR 10, mirrors ``tf.lm_prefill``). Causality
    keeps the attention rows exact; pad tokens are an exact identity on
    the mamba2 recurrences (``mamba._mask_dt``); Kg rows whose block
    contains any pad token are zeroed; the logits row is gathered at
    ``lengths - 1``."""
    n_units, period, rem = _plan(cfg)
    tokens = batch["tokens"]
    b, l = tokens.shape
    lengths = batch.get("lengths")                       # [B] | None
    x = jnp.take(params["embed"]["w"], tokens, axis=0)
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))

    def unit(x, unit_blocks):
        x, mstates = _mamba_scan(x, unit_blocks, cfg, collect_state=True,
                                 lengths=lengths)
        x, _, _, cache = tf.block_fwd_full(
            params["shared_attn"], x, cfg, rope_positions=pos,
            segment_ids=None, distill=False, collect_cache=True, shard=shard)
        return x, (mstates, cache)

    x, (mstates, caches) = layer_scan(unit, x, params["units"],
                                      unroll=not cfg.scan_layers)
    conv_u, h_u = mstates                  # [n_units, period, B, ...]
    conv = conv_u.reshape((-1,) + conv_u.shape[2:])
    h = h_u.reshape((-1,) + h_u.shape[2:])
    if rem:
        x, tail_states = _mamba_scan(x, params["tail"], cfg,
                                     collect_state=True, lengths=lengths)
        conv = jnp.concatenate([conv, tail_states[0]], axis=0)
        h = jnp.concatenate([h, tail_states[1]], axis=0)

    kr, v, kg = caches                     # [n_units, B, S, Hkv, Dh]
    pad = max_len - l
    # one-time seq-major -> head-major conversion (same as transformer)
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
                                (n_units, b)).astype(jnp.int32)
        if lengths is not None:
            # bucketed prefill: blocks touching pad tokens hold garbage Kg
            # rows — zero them (same staleness contract as tf.lm_prefill)
            row_ok = (jnp.arange(nb_max)[None, :]
                      < (cur_len // cfg.gate.block_size)[:, None])
            kg_cache = jnp.where(row_ok[None, :, None, :, None], kg_cache,
                                 jnp.zeros((), kg_cache.dtype))

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    last = (x[:, -1] if lengths is None
            else x[jnp.arange(b), jnp.maximum(cur_len - 1, 0)])
    logits = (last @ params["embed"]["w"].T if cfg.tie_embeddings
              else linear(params["lm_head"], last))
    st = HybridDecodeState(conv.astype(jnp.dtype(cfg.dtype)), h, k_cache,
                           v_cache, kg_cache, kg_n, cur_len)
    return logits, st


def lm_decode_step(params: Params, state: HybridDecodeState, token, cfg,
                   *, options=None, shard=None):
    """token [B] -> (logits, new state, aux) — see tf.lm_decode_step."""
    from repro.core.policy import default_options
    options = options if options is not None else default_options(cfg)
    n_units, period, rem = _plan(cfg)
    x1 = jnp.take(params["embed"]["w"], token[:, None], axis=0)

    def mamba_step_scan(x1, inp):
        bp, conv, h = inp
        y, (c2, h2) = mamba.mamba2_step(
            bp["mixer"], rms_norm(bp["ln"], x1, cfg.norm_eps), cfg, conv, h)
        return x1 + y, (c2, h2)

    lm = n_units * period
    conv_u = state.conv[:lm].reshape((n_units, period) + state.conv.shape[1:])
    h_u = state.h[:lm].reshape((n_units, period) + state.h.shape[1:])

    def unit(x1, inp):
        ublocks, uconv, uh, kc, vc, kgc, kgn = inp
        x1, (c2, h2) = layer_scan(mamba_step_scan, x1,
                                  (ublocks, uconv, uh),
                                  unroll=not cfg.scan_layers)
        x1, attn_state, aux = tf.block_decode(
            params["shared_attn"], x1, cfg,
            (kc, vc, kgc, kgn, None, None, None),   # no metacache: hybrid
            state.cur_len, options=options, shard=shard)
        return x1, ((c2, h2) + attn_state[:4], aux)

    x1, (outs, auxs) = layer_scan(unit, x1, (params["units"], conv_u, h_u,
                                             state.k_cache, state.v_cache,
                                             state.kg_cache, state.kg_n),
                                  unroll=not cfg.scan_layers)
    conv2, h2, kc, vc, kgc, kgn = outs
    conv2 = conv2.reshape((-1,) + conv2.shape[2:])
    h2 = h2.reshape((-1,) + h2.shape[2:])
    if rem:
        x1, (ct, ht) = layer_scan(
            mamba_step_scan, x1,
            (params["tail"], state.conv[lm:], state.h[lm:]),
            unroll=not cfg.scan_layers)
        conv2 = jnp.concatenate([conv2, ct], axis=0)
        h2 = jnp.concatenate([h2, ht], axis=0)

    x1 = rms_norm(params["final_norm"], x1, cfg.norm_eps)
    logits = (x1 @ params["embed"]["w"].T if cfg.tie_embeddings
              else linear(params["lm_head"], x1))
    new_state = HybridDecodeState(conv2.astype(state.conv.dtype), h2, kc, vc,
                                  kgc, kgn, state.cur_len + 1)
    return logits[:, 0], new_state, tf.aggregate_decode_aux(auxs)


def init_slot_state(cfg: ModelConfig, n_slots: int):
    """Zeroed per-slot recurrent state for the paged serving engine."""
    from repro.serve.slotstate import SlotState
    n_units, period, rem = _plan(cfg)
    di, hd, nh, n = mamba._m2_dims(cfg)
    lm = n_units * period + rem
    return SlotState(
        conv=jnp.zeros((lm, n_slots, cfg.ssm.conv_dim - 1, di + 2 * n),
                       jnp.dtype(cfg.dtype)),
        h=jnp.zeros((lm, n_slots, nh, hd, n), jnp.float32))


def lm_decode_step_paged(params: Params, pages, slot_state, token,
                         page_table, cur_len, active, cfg: ModelConfig, *,
                         options=None, budget_blocks=None, shard=None):
    """Continuous-batching decode step (PR 10 unified signature).

    The attention layer-core (``attn_core.block_decode_paged``) runs once
    per unit with the SHARED attention weights at that unit's layer index
    of the page pools (``[n_units, P, Hkv, ps, Dh]``, carried through the
    unit scan whole and written in place); the mamba2
    backbone steps update the per-slot recurrent ``slot_state`` rows.
    Inactive slots' recurrent updates are garbage but harmless — the
    engine rewrites their rows at admission/restore, exactly as it
    re-scatters their pages.
    """
    from repro.core.policy import default_options
    from repro.models.attn_core import (aggregate_decode_aux,
                                        block_decode_paged)
    options = options if options is not None else default_options(cfg)
    if options.schedule.needs_plan:
        raise NotImplementedError(
            "step-level selection plans assume a uniform self-attn stack; "
            "the hybrid family's single shared attention block re-selects "
            "every unit (schedule=SelectionSchedule())")
    n_units, period, rem = _plan(cfg)
    x1 = jnp.take(params["embed"]["w"], token[:, None], axis=0)

    def mamba_step_scan(x1, inp):
        bp, conv, h = inp
        y, (c2, h2) = mamba.mamba2_step(
            bp["mixer"], rms_norm(bp["ln"], x1, cfg.norm_eps), cfg, conv, h)
        return x1 + y, (c2, h2)

    lm = n_units * period
    conv_u = slot_state.conv[:lm].reshape(
        (n_units, period) + slot_state.conv.shape[1:])
    h_u = slot_state.h[:lm].reshape(
        (n_units, period) + slot_state.h.shape[1:])

    def unit(carry, inp):
        x1, pages = carry
        ublocks, uconv, uh, layer = inp
        x1, (c2, h2) = layer_scan(mamba_step_scan, x1,
                                  (ublocks, uconv, uh),
                                  unroll=not cfg.scan_layers)
        x1, pages, aux = block_decode_paged(
            params["shared_attn"], x1, cfg, pages, layer, page_table,
            cur_len, active, options=options, budget_blocks=budget_blocks,
            shard=shard)
        return (x1, pages), (c2, h2, aux)

    (x1, pages), (conv2, h2, auxs) = layer_scan(
        unit, (x1, pages),
        (params["units"], conv_u, h_u, jnp.arange(n_units, dtype=jnp.int32)),
        unroll=not cfg.scan_layers)
    conv2 = conv2.reshape((-1,) + conv2.shape[2:])
    h2 = h2.reshape((-1,) + h2.shape[2:])
    if rem:
        x1, (ct, ht) = layer_scan(
            mamba_step_scan, x1,
            (params["tail"], slot_state.conv[lm:], slot_state.h[lm:]),
            unroll=not cfg.scan_layers)
        conv2 = jnp.concatenate([conv2, ct], axis=0)
        h2 = jnp.concatenate([h2, ht], axis=0)

    x1 = rms_norm(params["final_norm"], x1, cfg.norm_eps)
    logits = (x1 @ params["embed"]["w"].T if cfg.tie_embeddings
              else linear(params["lm_head"], x1))
    return (logits[:, 0], pages,
            slot_state._replace(conv=conv2.astype(slot_state.conv.dtype),
                                h=h2),
            aggregate_decode_aux(auxs))
