"""Sparse-decode serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3_0_6b --reduced \
        [--batch 4] [--prefill 256] [--new 64] [--budget 128]
        [--method budget|threshold] [--dense]
        [--policy gate|quest|oracle|sliding_window]

Runs prefill + autoregressive decode through the SeerAttention-R engine
(KV cache + K-compression cache + selection policy + block-sparse
attention) and reports throughput and MEASURED achieved sparsity.
--policy swaps the block-selection strategy (core.policy); --dense
disables selection entirely for an A/B reference. Compiles persist in
JAX's compilation cache (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.config import reduced
from repro.core.policy import DecodeOptions, DensePolicy, get_policy
from repro.data.pipeline import DataState, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_api
from repro.serve.engine import DecodeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prefill", type=int, default=256)
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--budget", type=int, default=None)
    ap.add_argument("--method", default=None, choices=[None, "budget", "threshold"])
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--policy", default="gate",
                    choices=["gate", "quest", "quest_recompute", "oracle",
                             "sliding_window"])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    gate_kw = {}
    if args.budget is not None:
        gate_kw["token_budget"] = args.budget
    if args.method:
        gate_kw["method"] = args.method
    if gate_kw:
        cfg = cfg.replace(gate=dataclasses.replace(cfg.gate, **gate_kw))

    pol = get_policy(args.policy)
    # non-gate policies (quest/oracle/sliding_window) run fine without a
    # distilled gate; only GatePolicy needs cfg.gate.enabled
    sparse = (not args.dense) and cfg.has_attention and cfg.is_decoder \
        and (cfg.gate.enabled or not pol.needs_gate)
    opts = DecodeOptions(policy=pol if sparse else DensePolicy())
    params = get_api(cfg).init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prefill + args.new + 16
    batch = {"tokens": make_batch(cfg, args.batch, args.prefill,
                                  DataState(1, 0))["tokens"]}
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.zeros(
            (args.batch, cfg.n_image_tokens, cfg.d_model), jnp.dtype(cfg.dtype))

    eng = DecodeEngine(cfg, params, max_len=max_len, options=opts)
    res = eng.generate(batch, args.new)
    print(f"arch={cfg.arch_id} policy={args.policy if sparse else 'dense'} "
          f"devices={jax.device_count()}")
    print(f"prefill: {res['prefill_s'] * 1e3:.1f} ms | decode: "
          f"{res['decode_s'] * 1e3:.1f} ms | {res['tok_per_s']:.1f} tok/s")
    if sparse:
        stats = eng.sparsity_stats()      # measured over the decode above
        print(f"sparsity={stats['sparsity']:.3f} "
              f"io_speedup={stats['io_speedup']:.2f}x")


if __name__ == "__main__":
    main()
