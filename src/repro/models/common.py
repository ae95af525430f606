"""Shared model building blocks: norms, RoPE, linears, attention, MLPs.

Pure-functional JAX. Parameters are plain dict pytrees; initializers return
(params) and forward functions take (params, inputs). Sharding is attached
at the launch layer by path-name pattern rules (repro.distributed.sharding).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import Rope

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dtype(name: str):
    return jnp.dtype(name)


def init_linear(key, in_dim: int, out_dim: int, dtype="bfloat16",
                scale: Optional[float] = None) -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(in_dim)
    w = jax.random.normal(key, (in_dim, out_dim), dtype=jnp.float32) * scale
    return {"w": w.astype(_dtype(dtype))}


def linear(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return x @ p["w"]


def init_rmsnorm(d: int, dtype="bfloat16") -> Params:
    return {"scale": jnp.ones((d,), dtype=_dtype(dtype))}


def rms_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(dt)


# ---------------------------------------------------------------------------
# layer-stack scan (compact HLO) or unroll (exact cost_analysis)
# ---------------------------------------------------------------------------

def layer_scan(body, carry, xs, *, unroll: bool = False):
    """`jax.lax.scan` over stacked layer params, or a python unroll when
    ``unroll`` (cfg.scan_layers=False). Scan keeps the HLO compact at
    61-layer/1T scale; unroll makes XLA's cost_analysis count every layer
    (a `while` body is costed once), which the dry-run probe needs."""
    if not unroll:
        return jax.lax.scan(body, carry, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = body(carry, jax.tree_util.tree_map(lambda x: x[i], xs))
        ys.append(y)
    stacked = jax.tree_util.tree_map(lambda *zs: jnp.stack(zs), *ys)
    return carry, stacked


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, rope: Rope) -> jnp.ndarray:
    """Rotation rate per dimension pair; linear scaling divides it by the
    factor (the same angles as position / factor)."""
    freqs = 1.0 / (rope.theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                  / head_dim))
    if rope.factor != 1.0:
        freqs = freqs / rope.factor
    return freqs


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, rope: Rope
               ) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; positions: broadcastable to [..., seq].
    Negated positions undo the rotation of the same ``rope``."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, rope)                        # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs    # [..., seq, hd/2]
    cos = jnp.cos(ang)[..., None, :]                          # [..., seq, 1, hd/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / MLP
# ---------------------------------------------------------------------------

def init_glu_mlp(key, d_model: int, d_ff: int, dtype="bfloat16") -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": init_linear(k1, d_model, d_ff, dtype),
        "wi_up": init_linear(k2, d_model, d_ff, dtype),
        "wo": init_linear(k3, d_ff, d_model, dtype),
    }


def glu_mlp(p: Params, x: jnp.ndarray, activation: str = "swiglu") -> jnp.ndarray:
    g = linear(p["wi_gate"], x)
    if activation == "swiglu":
        g = jax.nn.silu(g)
    elif activation == "geglu":
        g = jax.nn.gelu(g, approximate=True)
    elif activation == "gelu":
        return linear(p["wo"], jax.nn.gelu(linear(p["wi_gate"], x), approximate=True))
    else:
        raise ValueError(activation)
    return linear(p["wo"], g * linear(p["wi_up"], x))


def init_mlp(key, d_model: int, d_ff: int, activation: str, dtype="bfloat16") -> Params:
    if activation in ("swiglu", "geglu"):
        return init_glu_mlp(key, d_model, d_ff, dtype)
    k1, k2 = jax.random.split(key)
    return {"wi_gate": init_linear(k1, d_model, d_ff, dtype),
            "wo": init_linear(k2, d_ff, d_model, dtype)}


def mlp(p: Params, x: jnp.ndarray, activation: str) -> jnp.ndarray:
    return glu_mlp(p, x, activation)


# ---------------------------------------------------------------------------
# attention (chunked online-softmax forward; doubles as the distillation-GT
# producer — see repro.core.distill for why block row-max is sufficient)
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def repeat_kv(x: jnp.ndarray, group: int) -> jnp.ndarray:
    """[B, S, Hkv, D] -> [B, S, Hkv*g, D] by repeating each kv head g times."""
    if group == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, group, d)).reshape(b, s, h * group, d)


def _softcap(s: jnp.ndarray, cap: float) -> jnp.ndarray:
    return jnp.tanh(s / cap) * cap if cap > 0 else s


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True,
                      q_positions: Optional[jnp.ndarray] = None,
                      kv_positions: Optional[jnp.ndarray] = None,
                      q_chunk: int = 1024,
                      logit_softcap: float = 0.0,
                      gt_block_size: int = 0,
                      segment_ids: Optional[jnp.ndarray] = None,
                      unroll_chunks: bool = False,
                      ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Memory-bounded attention forward with online softmax.

    q: [B, Lq, H, D]; k, v: [B, Lk, Hkv, D] (GQA expanded internally).
    Scans over q-chunks so the materialized score tensor is
    [B, H, q_chunk, Lk] instead of [B, H, Lq, Lk].

    If ``gt_block_size`` > 0 also returns the SeerAttention-R distillation
    ground-truth logits: per-(row, kv-block) max of the masked scores,
    shape [B, H, Lq, Lk // gt_block_size]  (softmax over the last axis of
    this equals the column-blockwise max-pool of the true attention map —
    the identity exploited by the paper's training kernel).
    """
    b, lq, h, d = q.shape
    lk, hkv = k.shape[1], k.shape[2]
    group = h // hkv
    k = repeat_kv(k, group)
    v = repeat_kv(v, group)
    if q_positions is None:
        q_positions = jnp.arange(lq)
    if kv_positions is None:
        kv_positions = jnp.arange(lk)
    scale = 1.0 / math.sqrt(d)

    qt = jnp.moveaxis(q, 2, 1)            # [B, H, Lq, D]
    kt = jnp.moveaxis(k, 2, 1)            # [B, H, Lk, D]
    vt = jnp.moveaxis(v, 2, 1)

    q_chunk = min(q_chunk, lq)
    n_chunks = -(-lq // q_chunk)
    pad = n_chunks * q_chunk - lq
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad), constant_values=lk + 1)
    qs = qt.reshape(b, h, n_chunks, q_chunk, d)
    qpos = q_positions.reshape(n_chunks, q_chunk)
    if segment_ids is not None:            # [B, Lq] == [B, Lk] (packed)
        qseg = jnp.pad(segment_ids, ((0, 0), (0, pad)), constant_values=-1) \
            if pad else segment_ids
        qseg = qseg.reshape(b, n_chunks, q_chunk)
    else:
        qseg = jnp.zeros((b, n_chunks, q_chunk), jnp.int32)

    nb = lk // gt_block_size if gt_block_size else 0

    def one_chunk(carry, inp):
        qc, qp, qsg = inp                  # [B,H,qc,D], [qc], [B,qc]
        s = jnp.einsum("bhqd,bhkd->bhqk", qc.astype(jnp.float32),
                       kt.astype(jnp.float32)) * scale
        s = _softcap(s, logit_softcap)
        if causal:
            mask = qp[:, None] >= kv_positions[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        if segment_ids is not None:
            smask = qsg[:, :, None] == segment_ids[:, None, :]   # [B,qc,Lk]
            s = jnp.where(smask[:, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, vt.astype(jnp.float32)) / jnp.maximum(l, 1e-30)
        if nb:
            # per-(row, kv-block) max logit; rows fully masked give NEG_INF
            gt = jnp.max(s.reshape(b, h, q_chunk, nb, gt_block_size), axis=-1)
        else:
            gt = jnp.zeros((b, h, q_chunk, 0), jnp.float32)
        return carry, (o, gt)

    # unroll_chunks: probe path (cfg.scan_layers=False) — XLA costs a scan
    # body once, so the q-chunk loop must unroll for exact cost_analysis
    _, (o, gt) = layer_scan(one_chunk, None,
                            (qs.swapaxes(0, 2).swapaxes(1, 2), qpos,
                             jnp.swapaxes(qseg, 0, 1)),
                            unroll=unroll_chunks)
    # o: [n_chunks, B, H, q_chunk, D] -> [B, Lq, H, D]
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n_chunks * q_chunk, d)[:, :, :lq]
    o = jnp.moveaxis(o, 1, 2).astype(q.dtype)
    if gt_block_size:
        gt = jnp.moveaxis(gt, 0, 2).reshape(b, h, n_chunks * q_chunk, nb)[:, :, :lq]
        return o, gt
    return o, None


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     kv_len: jnp.ndarray, *, logit_softcap: float = 0.0
                     ) -> jnp.ndarray:
    """Single-token dense decode attention.

    q: [B, 1, H, D]; caches: [B, Hkv, S, D] HEAD-MAJOR (the native decode
    layout — consumed directly, no transpose); kv_len: [B] valid lengths.
    """
    b, _, h, d = q.shape
    hkv, s_max = k_cache.shape[1], k_cache.shape[2]
    group = h // hkv
    qg = q[:, 0].reshape(b, hkv, group, d)                      # [B,Hkv,g,D]
    s = jnp.einsum("bhgd,bhsd->bhgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(d)
    s = _softcap(s, logit_softcap)
    valid = jnp.arange(s_max)[None, :] < kv_len[:, None]        # [B,S]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bhsd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """logits [B, L, V] fp32-safe CE with optional validity mask [B, L]."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
