"""Mixture-of-Experts FFN (DeepSeekMoE / Kimi-K2 style: shared + routed
fine-grained experts, top-k softmax routing).

Dispatch is sort/scatter based (NOT the GShard [T,E,C] one-hot einsum): at
kimi-k2 scale (E=384) the one-hot dispatch einsum costs T*E*C*d FLOPs —
more than the expert matmuls themselves. Here:

  1. top-k expert ids per token, flatten to N = T*k assignments
  2. stable argsort by expert id; rank-within-expert from cumulative counts
  3. scatter tokens into an [E, C(+1 overflow), d] buffer (capacity drop)
  4. batched per-expert GLU matmuls (einsum over the E axis — shardable
     over the 'model' mesh axis = expert parallelism)
  5. gather back by (expert, slot), weight by router probs, sum over k

Aux load-balance loss is the standard Switch  E * sum_e f_e * P_e.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import MoEConfig
from repro.models.common import init_glu_mlp, glu_mlp

Params = Dict[str, Any]
ShardFn = Optional[Callable[[jnp.ndarray, str], jnp.ndarray]]


def init_moe(key, d_model: int, mcfg: MoEConfig, activation: str = "swiglu",
             dtype="bfloat16") -> Params:
    ks = jax.random.split(key, 5)
    e, f = mcfg.n_experts, mcfg.expert_d_ff
    dt = jnp.dtype(dtype)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(f)
    p: Params = {
        "router": {"w": (jax.random.normal(ks[0], (d_model, e), jnp.float32)
                         * s_in).astype(jnp.float32)},  # router kept fp32
        "wi_gate": (jax.random.normal(ks[1], (e, d_model, f), jnp.float32) * s_in).astype(dt),
        "wi_up": (jax.random.normal(ks[2], (e, d_model, f), jnp.float32) * s_in).astype(dt),
        "wo": (jax.random.normal(ks[3], (e, f, d_model), jnp.float32) * s_out).astype(dt),
    }
    if mcfg.n_shared_experts:
        p["shared"] = init_glu_mlp(ks[4], d_model,
                                   mcfg.n_shared_experts * f, dtype)
    return p


def _rank_within_expert(flat_e: jnp.ndarray, n_experts: int) -> jnp.ndarray:
    """flat_e [N] expert ids -> [N] occurrence rank of each id (0-based)."""
    n = flat_e.shape[0]
    sort_idx = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[sort_idx]
    counts = jnp.zeros((n_experts,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - offsets[sorted_e]
    return jnp.zeros((n,), jnp.int32).at[sort_idx].set(rank_sorted)


def moe_mlp(p: Params, x: jnp.ndarray, mcfg: MoEConfig,
            activation: str = "swiglu", shard: ShardFn = None
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [T, d] tokens -> (y [T, d], aux_loss scalar).

    dispatch='shard_map' (and a mesh on ``shard``) takes the explicit EP
    path in moe_mlp_sharded; otherwise the GSPMD scatter path below.
    """
    mesh = getattr(shard, "mesh", None)
    if mcfg.dispatch == "shard_map" and mesh is not None \
            and "model" in mesh.axis_names:
        return moe_mlp_sharded(p, x, mcfg, activation, mesh,
                               ep_major=getattr(shard, "ep_major", False))
    t, d = x.shape
    e, k, f = mcfg.n_experts, mcfg.top_k, mcfg.expert_d_ff
    logits = (x.astype(jnp.float32) @ p["router"]["w"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                       # [T, k]
    top_w = top_p / jnp.maximum(jnp.sum(top_p, axis=-1, keepdims=True), 1e-9)

    n = t * k
    cap = max(1, int(math.ceil(n / e * mcfg.capacity_factor)))
    flat_e = top_i.reshape(n)
    rank = _rank_within_expert(flat_e, e)
    keep = rank < cap
    slot = jnp.where(keep, rank, cap)                            # cap = trash row

    x_rep = jnp.repeat(x, k, axis=0)                             # [N, d]
    buf = jnp.zeros((e, cap + 1, d), x.dtype).at[flat_e, slot].set(x_rep)
    if shard is not None:
        buf = shard(buf, "moe_buffer")
    xb = buf[:, :cap]                                            # [E, C, d]

    g = jnp.einsum("ecd,edf->ecf", xb, p["wi_gate"])
    u = jnp.einsum("ecd,edf->ecf", xb, p["wi_up"])
    act = jax.nn.silu(g) if activation == "swiglu" else jax.nn.gelu(g, approximate=True)
    yb = jnp.einsum("ecf,efd->ecd", act * u, p["wo"])            # [E, C, d]
    if shard is not None:
        yb = shard(yb, "moe_buffer")
    yb = jnp.concatenate([yb, jnp.zeros((e, 1, d), yb.dtype)], axis=1)

    y_rep = yb[flat_e, slot]                                     # [N, d]
    y_rep = jnp.where(keep[:, None], y_rep, 0)
    y = jnp.sum(y_rep.reshape(t, k, d) * top_w[..., None].astype(y_rep.dtype),
                axis=1)

    if "shared" in p:
        y = y + glu_mlp(p["shared"], x, activation)

    # Switch-style load-balance aux: E * sum_e (token fraction)*(prob mass)
    frac = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / n
    pmass = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * pmass) * mcfg.router_aux_coef
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# explicit EP dispatch (shard_map) — §Perf P2
# ---------------------------------------------------------------------------
#
# The GSPMD path above scatters every token into a GLOBAL [E, C, d] buffer;
# with tokens sharded over 'data' and experts over 'model', XLA lowers the
# scatter/gather pair into replicating collectives (TBs/step at 16b-MoE
# scale). The explicit pattern is the standard two-stage EP dispatch:
#
#   large T (train/prefill):
#     1. all-to-all over 'model' resplits the d-sharded activations into
#        full-feature token rows (T/(data*model) rows/device);
#     2. route + local scatter into [E, C_ll, d];
#     3. all-to-all over 'model' splits E -> local experts, concatenating
#        capacity: [E/m, C_ll*m, d]  (the dispatch traffic, ~T*k*d bytes);
#     4. per-expert GLU; reverse all-to-all; local gather+combine;
#     5. all-to-all back to the TP activation layout.
#   small T (decode): skip the resplit — replicate rows over 'model',
#     each shard computes ONLY its experts' contributions, combine = psum.

def _route(x_full, router_w, k):
    logits = x_full.astype(jnp.float32) @ router_w          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_w = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    return probs, top_i, top_w


def _expert_glu(wi_gate, wi_up, wo, xb, activation):
    g = jnp.einsum("ecd,edf->ecf", xb, wi_gate)
    u = jnp.einsum("ecd,edf->ecf", xb, wi_up)
    act = (jax.nn.silu(g) if activation == "swiglu"
           else jax.nn.gelu(g, approximate=True))
    return jnp.einsum("ecf,efd->ecd", act * u, wo)          # [E?, C, d]


def moe_mlp_sharded(p: Params, x: jnp.ndarray, mcfg: MoEConfig,
                    activation: str, mesh, ep_major: bool = False
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    from jax.sharding import PartitionSpec as P

    def smap(f, in_specs, out_specs):
        return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs)

    t, d = x.shape
    e, k, f = mcfg.n_experts, mcfg.top_k, mcfg.expert_d_ff
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n_dp = 1
    for a in dp:
        n_dp *= int(mesh.shape[a])
    n_m = int(mesh.shape["model"])
    dpa = dp if len(dp) > 1 else dp[0]
    t_shardable = t % n_dp == 0
    row_spec = dpa if t_shardable else None
    t_loc = t // n_dp if t_shardable else t
    e_loc = e // n_m
    assert e % n_m == 0, "experts must divide the model axis"
    big_t = t_loc % n_m == 0 and (t_loc // n_m) * k >= e

    wspec = (P(row_spec, "model"), P(None, None),
             P("model", None, None), P("model", None, None),
             P("model", None, None))

    full_axes = dp + ("model",)
    n_full = n_dp * n_m
    if ep_major and t % n_full == 0:
        # EP-major (§Perf P2 iter 2): rows already sharded over
        # (data x model) with FULL d — no TP resplit needed; the only
        # collective is the dispatch all-to-all over 'model'.
        t_ll = t // n_full
        cap = max(1, int(math.ceil(t_ll * k / e * mcfg.capacity_factor)))

        def body(xf, router_w, wi_gate, wi_up, wo):
            probs, top_i, top_w = _route(xf, router_w, k)
            tl = xf.shape[0]
            n = tl * k
            flat_e = top_i.reshape(n)
            rank = _rank_within_expert(flat_e, e)
            keep = rank < cap
            slot = jnp.where(keep, rank, cap)
            x_rep = jnp.repeat(xf, k, axis=0)
            buf = jnp.zeros((e, cap + 1, d), xf.dtype).at[flat_e, slot].set(x_rep)
            buf = buf[:, :cap]
            be = jax.lax.all_to_all(buf, "model", split_axis=0,
                                    concat_axis=1, tiled=True)
            yb = _expert_glu(wi_gate, wi_up, wo, be, activation)
            yb = jax.lax.all_to_all(yb, "model", split_axis=1,
                                    concat_axis=0, tiled=True)
            yb = jnp.concatenate([yb, jnp.zeros((e, 1, d), yb.dtype)], axis=1)
            y_rep = yb[flat_e, slot]
            y_rep = jnp.where(keep[:, None], y_rep, 0)
            y = jnp.sum(y_rep.reshape(tl, k, d)
                        * top_w[..., None].astype(y_rep.dtype), axis=1)
            frac = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / n
            pmass = jnp.mean(probs, axis=0)
            frac = jax.lax.pmean(frac, "model")
            pmass = jax.lax.pmean(pmass, "model")
            if dp:
                frac = jax.lax.pmean(frac, dp)
                pmass = jax.lax.pmean(pmass, dp)
            aux = e * jnp.sum(frac * pmass) * mcfg.router_aux_coef
            return y, aux

        rs = full_axes if len(full_axes) > 1 else full_axes[0]
        ep_wspec = (P(rs, None), P(None, None), P("model", None, None),
                    P("model", None, None), P("model", None, None))
        y, aux = smap(body, ep_wspec, (P(rs, None), P()))(
            x, p["router"]["w"], p["wi_gate"], p["wi_up"], p["wo"])
        if "shared" in p:
            y = y + glu_mlp(p["shared"], x, activation)
        return y.astype(x.dtype), aux

    if big_t:
        cap = max(1, int(math.ceil(t_loc // n_m * k / e * mcfg.capacity_factor)))

        def body(x_loc, router_w, wi_gate, wi_up, wo):
            # x_loc [t_loc, d/m] -> resplit to full rows [t_loc/m, d]
            xf = jax.lax.all_to_all(x_loc, "model", split_axis=0,
                                    concat_axis=1, tiled=True)
            probs, top_i, top_w = _route(xf, router_w, k)
            tl = xf.shape[0]
            n = tl * k
            flat_e = top_i.reshape(n)
            rank = _rank_within_expert(flat_e, e)
            keep = rank < cap
            slot = jnp.where(keep, rank, cap)
            x_rep = jnp.repeat(xf, k, axis=0)
            buf = jnp.zeros((e, cap + 1, d), xf.dtype).at[flat_e, slot].set(x_rep)
            buf = buf[:, :cap]                               # [E, C_ll, d]
            # dispatch: E -> local experts, concat capacity
            be = jax.lax.all_to_all(buf, "model", split_axis=0,
                                    concat_axis=1, tiled=True)  # [E/m, C_ll*m, d]
            yb = _expert_glu(wi_gate, wi_up, wo, be, activation)
            yb = jax.lax.all_to_all(yb, "model", split_axis=1,
                                    concat_axis=0, tiled=True)  # [E, C_ll, d]
            yb = jnp.concatenate([yb, jnp.zeros((e, 1, d), yb.dtype)], axis=1)
            y_rep = yb[flat_e, slot]
            y_rep = jnp.where(keep[:, None], y_rep, 0)
            y = jnp.sum(y_rep.reshape(tl, k, d)
                        * top_w[..., None].astype(y_rep.dtype), axis=1)
            # back to the TP layout [t_loc, d/m]
            y = jax.lax.all_to_all(y, "model", split_axis=1,
                                   concat_axis=0, tiled=True)
            frac = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / n
            frac = jax.lax.pmean(frac, "model")
            pmass = jax.lax.pmean(jnp.mean(probs, axis=0), "model")
            if dp:
                frac = jax.lax.pmean(frac, dp)
                pmass = jax.lax.pmean(pmass, dp)
            aux = e * jnp.sum(frac * pmass) * mcfg.router_aux_coef
            return y, aux

        y, aux = smap(body, wspec, (P(row_spec, "model"), P()))(
            x, p["router"]["w"], p["wi_gate"], p["wi_up"], p["wo"])
    else:
        # decode-size T: replicate rows over 'model'; each shard computes
        # only its local experts' contributions; combine with one psum.
        cap = max(1, int(math.ceil(t_loc * k / e * mcfg.capacity_factor)))

        def body(x_loc, router_w, wi_gate, wi_up, wo):
            xf = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
            probs, top_i, top_w = _route(xf, router_w, k)
            tl = xf.shape[0]
            n = tl * k
            flat_e = top_i.reshape(n)
            rank = _rank_within_expert(flat_e, e)
            keep = rank < cap
            slot = jnp.where(keep, rank, cap)
            m_idx = jax.lax.axis_index("model")
            e0 = m_idx * e_loc
            local = (flat_e >= e0) & (flat_e < e0 + e_loc) & keep
            lslot = jnp.where(local, slot, cap)
            le = jnp.clip(flat_e - e0, 0, e_loc - 1)
            x_rep = jnp.repeat(xf, k, axis=0)
            buf = jnp.zeros((e_loc, cap + 1, d), xf.dtype).at[le, lslot].set(
                jnp.where(local[:, None], x_rep, 0))
            yb = _expert_glu(wi_gate, wi_up, wo, buf[:, :cap], activation)
            yb = jnp.concatenate([yb, jnp.zeros((e_loc, 1, d), yb.dtype)], 1)
            y_rep = jnp.where(local[:, None], yb[le, lslot], 0)
            y = jnp.sum(y_rep.reshape(tl, k, d)
                        * top_w[..., None].astype(y_rep.dtype), axis=1)
            y = jax.lax.psum(y, "model")
            frac = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / n
            pmass = jnp.mean(probs, axis=0)
            # identical on every model shard (same gathered rows) — the
            # pmean is a no-op numerically but proves replication to vma
            frac = jax.lax.pmean(frac, "model")
            pmass = jax.lax.pmean(pmass, "model")
            if dp:
                frac = jax.lax.pmean(frac, dp)
                pmass = jax.lax.pmean(pmass, dp)
            aux = e * jnp.sum(frac * pmass) * mcfg.router_aux_coef
            # return rows in the TP layout
            y = y.reshape(tl, n_m, d // n_m)[:, m_idx]
            return y, aux

        y, aux = smap(body, wspec, (P(row_spec, "model"), P()))(
            x, p["router"]["w"], p["wi_gate"], p["wi_up"], p["wo"])

    if "shared" in p:
        y = y + glu_mlp(p["shared"], x, activation)
    return y.astype(x.dtype), aux
