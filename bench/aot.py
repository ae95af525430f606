#!/usr/bin/env python3
"""Compile a cell's decode step and its largest prefill bucket for a
described TPU v5e (no chip needed) and print ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload <cell>

The programs are the ones ``DecodeEngine.serve`` runs for the cell: the
paged decode step with the Pallas kernels at the cell's slots, pool,
page table and budget, and ``lm_prefill`` at the largest power-of-two
page bucket the mix's prompts fall in.
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from harness.model import decode_options, param_shapes, program_config
    from harness.spec import Spec
    from repro.models.registry import get_api
    from repro.serve import paging as pg
    from repro.serve.scheduler import pages_needed
    jax.config.update("jax_enable_compilation_cache", False)
    spec = Spec(BENCH.parent)
    cell = spec.cell(args.workload)
    conf = spec.config(cell["config"])
    mix = spec.mix(cell["traffic"])
    cfg = program_config(conf)
    opts = decode_options(cfg, conf).replace(kernel_impl="pallas")
    api = get_api(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one), tree)

    ps = cfg.gate.block_size
    slots, pool = int(mix["clients"]), int(mix["pool_pages"])
    npt = pages_needed(mix["prompt_tokens"][1], mix["new_tokens"][1], ps)
    params = on_chip(param_shapes(cfg))
    pages = on_chip(jax.eval_shape(lambda: pg.init_pages(
        cfg, pool, api.paged_attn_layers(cfg),
        with_meta=opts.policy.needs_meta, quantize=opts.quantize)))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one)
    step = jax.jit(functools.partial(api.decode_step_paged, cfg=cfg,
                                     options=opts, shard=None),
                   donate_argnums=(1,))
    compiled = step.lower(
        params, pages, None, i32((slots,)), i32((slots, npt)),
        i32((slots,)), jax.ShapeDtypeStruct((slots,), jnp.bool_,
                                            sharding=one),
        budget_blocks=i32((slots,))).compile()
    report("decode_step", compiled)
    bucket = 1 << (-(-mix["prompt_tokens"][1] // ps) - 1).bit_length()
    prefill = jax.jit(functools.partial(api.prefill, cfg=cfg,
                                        max_len=bucket * ps, options=opts))
    compiled = prefill.lower(params, {"tokens": i32((1, bucket * ps)),
                                      "lengths": i32((1,))}).compile()
    report(f"prefill_{bucket * ps}", compiled)
    return 0


def report(name, compiled) -> None:
    m = compiled.memory_analysis()
    kernels = compiled.as_text().count("tpu_custom_call")
    print(f"{name}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
          f"outputs {m.output_size_in_bytes / 1e9:.3f} GB, "
          f"aliased {m.alias_size_in_bytes / 1e9:.3f} GB, "
          f"temp {m.temp_size_in_bytes / 1e9:.3f} GB, "
          f"tpu_custom_call {kernels}", flush=True)


if __name__ == "__main__":
    sys.exit(main())
