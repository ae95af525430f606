"""The system under test, built from a configuration file.

A configuration file (``bench/configs/<name>.json``) holds the model's
sizes under their published (Hugging Face ``config.json``) keys, as they
are run, plus the gate and the deployment. ``program_config`` maps those
keys onto the program's ``ModelConfig`` (starting from the program's own
config of the same family, ``program_base``) and checks that what the
program will run is exactly what the file says. ``make_params`` makes the
weights from the seed on the device, in one jitted call, in the dtype they
are served in; the program's own initializer is consulted only for the
layout of its parameter tree (names and shapes), never for values.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

# published key -> ModelConfig field
HF_KEYS = {
    "hidden_size": "d_model",
    "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "torch_dtype": "dtype",
}
GATE_KEYS = ("block_size", "d_gate", "rope_theta", "use_rope",
             "always_first_block", "always_last_block")


def program_config(conf: Dict[str, Any]):
    """Configuration file -> the program's ModelConfig, checked key by key."""
    from repro import configs
    base = configs.get(conf["program_base"])
    over = {field: conf[key] for key, field in HF_KEYS.items() if key in conf}
    over["qk_norm"] = bool(conf["qk_norm"])
    gate = conf["gate"]
    over["gate"] = dataclasses.replace(
        base.gate, enabled=True, method="budget",
        token_budget=int(conf["budget_tokens"]),
        dense_first_layers=0, **{k: gate[k] for k in GATE_KEYS})
    cfg = base.replace(**over)
    for key, field in HF_KEYS.items():
        if key in conf and getattr(cfg, field) != conf[key]:
            raise ValueError(f"{key}: program runs {getattr(cfg, field)!r}, "
                             f"file says {conf[key]!r}")
    if cfg.family != "dense" or cfg.activation != "swiglu":
        raise ValueError(f"{conf['name']}: the reference covers dense swiglu "
                         f"decoders, not {cfg.family}/{cfg.activation}")
    return cfg


def decode_options(cfg, conf: Dict[str, Any]):
    """The config's default DecodeOptions plus the deployment's budget
    (and kernel path, where the file names one)."""
    from repro.core.policy import default_options
    opts = default_options(cfg).replace(
        budget_override=int(conf["budget_tokens"]))
    if conf.get("kernel_impl"):
        opts = opts.replace(kernel_impl=conf["kernel_impl"])
    return opts


def param_shapes(cfg):
    import jax
    from repro.models.registry import get_api
    return jax.eval_shape(lambda k: get_api(cfg).init_params(k, cfg),
                          jax.random.PRNGKey(0))


def _leaf_init(path: Tuple[str, ...], shape, dtype, key):
    """Norm scales are ones; the embedding is N(0, 0.02); every other
    weight is N(0, 1/fan_in), fan_in being its second-to-last axis."""
    import jax
    import jax.numpy as jnp
    if path[-1] == "scale":
        return jnp.ones(shape, dtype)
    std = 0.02 if path[0] == "embed" else 1.0 / math.sqrt(shape[-2])
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(cfg, seed_key):
    """Weights from ``seed_key`` in one jitted call, on the device."""
    import jax
    shapes = param_shapes(cfg)
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    paths = [tuple(getattr(k, "key", str(k)) for k in p) for p, _ in flat]

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(tree, [
            _leaf_init(path, leaf.shape, leaf.dtype, k)
            for path, (_, leaf), k in zip(paths, flat, keys)])

    return init(seed_key)
