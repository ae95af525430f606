"""SeerAttention-R AttnGate (decode variant).

The gate predicts, for each new query token, a score per KV *block*:

  Q branch (eq. 1a): the ``g`` query heads of a GQA group are concatenated
    and reduced by a per-KV-head learned linear [g*d_head -> d_gate]; RoPE is
    re-applied (gate consumes *pre-rope* Q).  No sequence pooling — decode is
    token-by-token.
  K branch (eq. 1b): keys are chunked into non-overlapping blocks of
    ``block_size``; max/min/avg pooling over each block are concatenated
    ([3*d_head]) and mapped by a per-KV-head linear to d_gate; RoPE uses the
    position of the first token of each block.
  Score (eq. 1c): softmax(Qg Kg^T / sqrt(d_gate)) over blocks.

All functions are batch-first: Q [B, L, H, Dh], K [B, S, Hkv, Dh].
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import GateConfig
from repro.models.common import NEG_INF, apply_rope

Params = Dict[str, Any]


def init_attngate(key, *, n_kv_heads: int, group: int, head_dim: int,
                  cfg: GateConfig, dtype="bfloat16") -> Params:
    """Per-layer gate parameters.

    wq: [Hkv, g*Dh, Dg]   (one set of weights per GQA group — paper §2.2)
    wk: [Hkv, 3*Dh, Dg]   (K-branch linear after max/min/avg pool concat)
    """
    kq, kk = jax.random.split(key)
    dg = cfg.d_gate
    sq = 1.0 / math.sqrt(group * head_dim)
    sk = 1.0 / math.sqrt(3 * head_dim)
    wq = jax.random.normal(kq, (n_kv_heads, group * head_dim, dg), jnp.float32) * sq
    wk = jax.random.normal(kk, (n_kv_heads, 3 * head_dim, dg), jnp.float32) * sk
    return {"wq": wq.astype(jnp.dtype(dtype)), "wk": wk.astype(jnp.dtype(dtype))}


def gate_q(params: Params, q_nope: jnp.ndarray, positions: jnp.ndarray,
           cfg: GateConfig) -> jnp.ndarray:
    """q_nope: [B, L, H, Dh] pre-rope queries -> Qg [B, L, Hkv, Dg]."""
    b, l, h, dh = q_nope.shape
    hkv = params["wq"].shape[0]
    g = h // hkv
    qr = q_nope.reshape(b, l, hkv, g * dh)
    qg = jnp.einsum("blhe,hed->blhd", qr, params["wq"])
    if cfg.use_rope:
        qg = apply_rope(qg, positions, cfg.rope)
    return qg


def pool_k_blocks(k_nope: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """k_nope: [B, S, Hkv, Dh] (S divisible by block_size)
    -> pooled [B, nb, Hkv, 3*Dh] = concat(max, min, avg) over each block."""
    b, s, hkv, dh = k_nope.shape
    nb = s // block_size
    kb = k_nope.reshape(b, nb, block_size, hkv, dh)
    kmax = jnp.max(kb, axis=2)
    kmin = jnp.min(kb, axis=2)
    kavg = jnp.mean(kb.astype(jnp.float32), axis=2).astype(k_nope.dtype)
    return jnp.concatenate([kmax, kmin, kavg], axis=-1)


def gate_k(params: Params, k_nope: jnp.ndarray, cfg: GateConfig,
           first_block_index: int = 0) -> jnp.ndarray:
    """k_nope: [B, S, Hkv, Dh] -> Kg [B, nb, Hkv, Dg].

    ``first_block_index`` offsets RoPE positions (used when incrementally
    extending the K-compression cache during decode).
    """
    pooled = pool_k_blocks(k_nope, cfg.block_size)       # [B, nb, Hkv, 3Dh]
    kg = jnp.einsum("bnhe,hed->bnhd", pooled, params["wk"])
    if cfg.use_rope:
        nb = kg.shape[1]
        pos = (first_block_index + jnp.arange(nb)) * cfg.block_size
        kg = apply_rope(kg, pos, cfg.rope)
    return kg


def gate_logits(qg: jnp.ndarray, kg: jnp.ndarray) -> jnp.ndarray:
    """Qg [B, L, Hkv, Dg] x Kg [B, nb, Hkv, Dg] -> [B, Hkv, L, nb] (fp32)."""
    dg = qg.shape[-1]
    return jnp.einsum("blhd,bnhd->bhln", qg.astype(jnp.float32),
                      kg.astype(jnp.float32)) / math.sqrt(dg)


def block_causal_mask(q_positions: jnp.ndarray, n_blocks: int,
                      block_size: int) -> jnp.ndarray:
    """[L, nb] True where block ``j`` contains any position <= q position.

    A block is visible once its FIRST token is in the past (the trailing
    partial block is handled by force-selecting the last block, §3.2).
    """
    starts = jnp.arange(n_blocks) * block_size
    return q_positions[:, None] >= starts[None, :]


def gate_scores(qg: jnp.ndarray, kg: jnp.ndarray, *,
                q_positions: jnp.ndarray, block_size: int,
                softmax: bool = True) -> jnp.ndarray:
    """Masked gate scores [B, Hkv, L, nb]; softmax over blocks if requested
    (the budget/top-k path can skip softmax — paper §3.1)."""
    s = gate_logits(qg, kg)
    mask = block_causal_mask(q_positions, kg.shape[1], block_size)
    s = jnp.where(mask[None, None], s, NEG_INF)
    return jax.nn.softmax(s, axis=-1) if softmax else s
