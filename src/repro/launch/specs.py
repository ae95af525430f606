"""Abstract input construction for the multi-pod dry-run.

``cell_fn_and_specs(cfg, shape, mesh, tcfg)`` returns (step_fn, abstract
args) where every arg is a ShapeDtypeStruct carrying its NamedSharding —
``jax.jit(step_fn).lower(*args)`` then compiles the production program with
zero real allocation (the shannon/kernels ShapeDtypeStruct pattern).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ShapeConfig, TrainConfig
from repro.distributed import sharding as shd
from repro.models.registry import get_api
from repro.train import loop as train_loop


def _sds(shape, dtype, mesh=None, spec=None):
    if mesh is not None:
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec or P()))
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_batch(cfg: ModelConfig, bsz: int, slen: int, mesh: Mesh
                   ) -> Dict[str, jax.ShapeDtypeStruct]:
    b = shd.batch_pspecs(bsz, mesh, getattr(cfg, "ep_major", False))
    t = lambda *rest: P(*((b,) + rest))
    if cfg.family == "audio":
        return {
            "features": _sds((bsz, slen, cfg.n_audio_features),
                             jnp.dtype(cfg.dtype), mesh, t(None, None)),
            "labels": _sds((bsz, slen), jnp.int32, mesh, t(None)),
        }
    out = {
        "tokens": _sds((bsz, slen), jnp.int32, mesh, t(None)),
        "labels": _sds((bsz, slen), jnp.int32, mesh, t(None)),
        "segment_ids": _sds((bsz, slen), jnp.int32, mesh, t(None)),
        "positions": _sds((bsz, slen), jnp.int32, mesh, t(None)),
        "loss_mask": _sds((bsz, slen), jnp.float32, mesh, t(None)),
    }
    if cfg.family == "vlm":
        out["image_embeds"] = _sds((bsz, cfg.n_image_tokens, cfg.d_model),
                                   jnp.dtype(cfg.dtype), mesh, t(None, None))
    return out


def _with_shardings(abstract: Any, specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        abstract, specs)


def abstract_params(cfg: ModelConfig, mesh: Mesh):
    api = get_api(cfg)
    p_abs = jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))
    specs = shd.param_pspecs(p_abs, cfg, mesh)
    return _with_shardings(p_abs, specs, mesh), specs


def abstract_train_state(cfg: ModelConfig, tcfg: TrainConfig, mesh: Mesh):
    st_abs = jax.eval_shape(
        functools.partial(train_loop.init_train_state, cfg=cfg, tcfg=tcfg),
        jax.random.PRNGKey(0))
    pspecs = shd.param_pspecs(st_abs.params, cfg, mesh)
    if tcfg.mode == "distill":
        gate_specs = jax.tree.map(lambda _: P(), st_abs.gate)
        opt_target = gate_specs
    else:
        gate_specs = None
        opt_target = shd.zero1_param_pspecs(st_abs.params, mesh, cfg)
    opt_specs = type(st_abs.opt)(
        m=opt_target, v=opt_target, count=P(),
        ef=(opt_target if st_abs.opt.ef is not None else None))
    specs = train_loop.TrainState(pspecs, gate_specs, opt_specs, P())
    return _with_shardings(st_abs, specs, mesh), specs


def abstract_decode_state(cfg: ModelConfig, bsz: int, max_len: int,
                          mesh: Mesh):
    api = get_api(cfg)
    st_abs = jax.eval_shape(
        functools.partial(api.init_decode_state, cfg, bsz, max_len))
    specs = shd.decode_state_pspecs(st_abs, bsz, mesh)
    return _with_shardings(st_abs, specs, mesh), specs


# ---------------------------------------------------------------------------
# cell -> (fn, abstract args)
# ---------------------------------------------------------------------------

def default_train_cfg(cfg: ModelConfig) -> TrainConfig:
    gate_on = cfg.gate.enabled and cfg.has_attention and cfg.is_decoder
    return TrainConfig(mode="distill" if gate_on else "pretrain")


def cell_fn_and_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                      tcfg: TrainConfig = None) -> Tuple[Callable, Tuple]:
    api = get_api(cfg)
    shard = shd.make_shard_fn(mesh, getattr(cfg, "ep_major", False))

    if shape.kind == "train":
        tcfg = tcfg or default_train_cfg(cfg)
        step = train_loop.make_train_step(cfg, tcfg, shard=shard)
        state_abs, _ = abstract_train_state(cfg, tcfg, mesh)
        batch_abs = abstract_batch(cfg, shape.global_batch, shape.seq_len, mesh)
        return step, (state_abs, batch_abs)

    if shape.kind == "prefill":
        params_abs, _ = abstract_params(cfg, mesh)
        batch_abs = abstract_batch(cfg, shape.global_batch, shape.seq_len, mesh)
        if not cfg.is_decoder:
            # encoder-only (hubert): "prefill" == full encoder forward
            def encoder_step(params, batch):
                return api.forward(params, batch, cfg, mode="pretrain",
                                   shard=shard)
            return encoder_step, (params_abs, batch_abs)

        def prefill_step(params, batch):
            return api.prefill(params, batch, cfg, shape.seq_len, shard=shard)
        batch_abs.pop("labels", None)
        batch_abs.pop("loss_mask", None)
        batch_abs.pop("segment_ids", None)
        batch_abs.pop("positions", None)
        return prefill_step, (params_abs, batch_abs)

    if shape.kind == "decode":
        import os
        from repro.core.policy import default_options
        # telemetry off: the dry-run probes cost the decode DATA PATH,
        # matching the bench_decode hot-path discipline
        opts = default_options(cfg).replace(
            kernel_impl=os.environ.get("REPRO_SERVE_IMPL"),
            measure_sparsity=False)

        def serve_step(params, state, token):
            return api.decode_step(params, state, token, cfg, options=opts,
                                   shard=shard)
        # serving engines donate the decode state: cache updates alias in
        # place instead of copying the full KV cache every step.
        serve_step.donate_argnums = (1,)
        params_abs, _ = abstract_params(cfg, mesh)
        state_abs, _ = abstract_decode_state(cfg, shape.global_batch,
                                             shape.seq_len, mesh)
        tok_abs = _sds((shape.global_batch,), jnp.int32, mesh,
                       P(shd.batch_pspecs(shape.global_batch, mesh)))
        return serve_step, (params_abs, state_abs, tok_abs)

    raise ValueError(shape.kind)
