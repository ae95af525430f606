#!/usr/bin/env python3
"""Chip smoke test: full-width qwen3_0_6b served by ``DecodeEngine.serve``
on the paged Pallas decode path, checked against the jnp reference path.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the paged x sharded path

One chip. The published qwen3_0_6b (28 layers, d_model 1024, 16 Q / 8 KV
heads, head_dim 128, vocab 151936, gate block 64, d_gate 128) in bf16,
with random weights from a fixed seed, serves 8 seeded ragged requests
(prompts of 2048-4096 tokens, 64-128 new tokens) in 8 slots with lazy
admission and a 640-page pool (about 4.7 GB of KV). Phases:

  pallas       the compiled Pallas kernels (kernel_impl="pallas") at a
               512-token budget (8 of at least 32 visible blocks);
  ref          the jnp kernels (kernel_impl="ref"), the reference;
  pallas_full, ref_full, int8_full
               the same requests, 32 new tokens each, at a budget that
               covers every visible block, on fp pools (Pallas and jnp)
               and on int8 pools (Pallas).

Each phase must retire every request clean with finite logits; the
sparse phases must measure a sparsity above 0.5 for every request, and
the decode step the pallas phase runs must contain ``tpu_custom_call``
(the Mosaic kernels, not a reference). Two runs are compared over each
request's rows up to and including its first diverging greedy token (the
rows whose context both runs share): the first generated token must
agree, and max|dlogit| / max|logit| is printed.

  pallas vs ref           at the 512-token budget: printed, not bounded.
                          The gate's weights are random, so its block
                          scores are nearly tied; a rounding-level
                          difference in the layers before flips a
                          selection, and later layers and steps compound
                          the flips.
  pallas_full vs ref_full and int8_full vs pallas_full
                          bounded by 0.05 over at least 2 rows per
                          request: with every visible block selected there
                          is no choice to flip, so the served path itself
                          (selection and attention kernels, every decode
                          step) is held to the bound.

Four chips (``--chips 4``). The same requests on a (1, 4) ("data",
"model") mesh with kernel_impl="sharded" (each chip holds 2 of the 8 KV
heads) against the unsharded Pallas engine in the same process, bounded
the same way; the page pools must span all four devices.

Compiles are kept in JAX's persistent cache (``JAX_COMPILATION_CACHE_DIR``
when set, else ``<repo>/.jax_cache``), so a second run reports a shorter
setup. Each phase prints a line of its numbers; the last line is one JSON
object naming the device. Without a TPU, outside a checkout, or when a
check fails, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
N_REQUESTS = 8
N_SLOTS = 8
POOL_PAGES = 640
BUDGET = 512                  # tokens: 8 blocks of 64
PROMPT_LEN = (2048, 4096)
NEW_TOKENS = (64, 128)
MAX_LEN = PROMPT_LEN[1] + NEW_TOKENS[1]   # also the every-block budget
FULL_NEW_TOKENS = 32          # the every-block phases decode a shorter stretch
LOGIT_BOUND = 0.05            # x max|logit| (tests/test_quant.py's bound)
MIN_ROWS = 2                  # compared rows per request, at least
MIN_SPARSITY = 0.5


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def make_requests(vocab: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    reqs = []
    for rid in range(N_REQUESTS):
        plen = int(rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1))
        new = int(rng.integers(NEW_TOKENS[0], NEW_TOKENS[1] + 1))
        reqs.append({"rid": rid, "max_new_tokens": new,
                     "tokens": rng.integers(0, vocab, size=plen,
                                            dtype=np.int32)})
    return reqs


def serve_phase(name, eng, reqs, sparse=True):
    """Warm up (compile every prefill bucket and the decode step the
    requests need), then serve them and check the run (sparsity only when
    ``sparse``). Returns the serve result."""
    import numpy as np
    from repro.serve.scheduler import pages_needed
    ps = eng.cfg.gate.block_size
    npt = max(pages_needed(len(r["tokens"]), r["max_new_tokens"], ps)
              for r in reqs)
    buckets = sorted({1 << (-(-len(r["tokens"]) // ps) - 1).bit_length()
                      for r in reqs})
    warm = [{"rid": i, "max_new_tokens": 2,
             "tokens": np.zeros((b * ps,), np.int32)}
            for i, b in enumerate(buckets)]
    kw = dict(n_slots=N_SLOTS, num_pages=POOL_PAGES, admission="lazy")
    t0 = time.perf_counter()
    eng.serve(warm, table_pages=npt, **kw)
    setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = eng.serve([dict(r) for r in reqs], collect_logits=True, **kw)
    wall = time.perf_counter() - t0
    st = res["stats"]
    rho = st["sparsity_by_rid"]
    print(f"[{name}] setup_s={setup:.1f} wall_s={wall:.2f} "
          f"generated_tokens={st['generated_tokens']} "
          f"decode_steps={st['decode_steps']} "
          f"retired_clean={st['retired_clean']}/{len(reqs)} "
          f"errors={st['errors']} preemptions={st['preemptions']} "
          f"peak_pages={st['peak_pages_used']}/{POOL_PAGES} "
          f"pool_devices={st['pool_devices']} "
          f"sparsity_min={min(rho.values(), default=0.0):.3f} "
          f"sparsity_max={max(rho.values(), default=0.0):.3f}", flush=True)
    rids = [r["rid"] for r in reqs]
    check(st["retired_clean"] == len(reqs) and not st["errors"]
          and st["failed"] == 0, f"{name}: not every request retired clean")
    for r in reqs:
        rid = r["rid"]
        check(len(res[rid]) == r["max_new_tokens"],
              f"{name}: rid {rid} generated {len(res[rid])} tokens")
        check(bool(np.isfinite(res["logits"][rid]).all()),
              f"{name}: rid {rid} has non-finite logits")
    check(not sparse or (sorted(rho) == sorted(rids) and all(
        rho[rid] > MIN_SPARSITY for rid in rids)),
          f"{name}: measured sparsity not above {MIN_SPARSITY} for every "
          f"rid: {rho}")
    return res


def compare(name, ref, test, reqs, *, bounded: bool):
    """Compare two served runs request by request. The first generated
    token of every request must agree. Row i of a request's logits chose
    token i from tokens 0..i-1, so the rows through the first diverging
    greedy token see the same context on both sides; over those rows
    max|dlogit| / max|logit| is printed and, when ``bounded``, must be at
    most LOGIT_BOUND, over at least MIN_ROWS rows per request. Row 1 (the
    first decode step: prefill is shared) is printed on its own."""
    import numpy as np
    first = rows = total = 0
    worst = (0.0, 0.0, 0.0)              # (ratio, max|dlogit|, max|logit|)
    row1, upto_by_rid = [], []
    for r in reqs:
        rid = r["rid"]
        a, b = np.asarray(ref[rid]), np.asarray(test[rid])
        n = min(len(a), len(b))
        diverged = np.nonzero(a[:n] != b[:n])[0]
        upto = int(diverged[0]) + 1 if diverged.size else n
        la, lb = ref["logits"][rid], test["logits"][rid]
        d = float(np.max(np.abs(la[:upto] - lb[:upto])))
        scale = float(np.max(np.abs(la[:upto])))
        worst = max(worst, (d / scale, d, scale))
        if n > 1:
            row1.append(float(np.max(np.abs(la[1] - lb[1]))
                              / np.max(np.abs(la[1]))))
        first += int(a[0] == b[0])
        rows += upto
        total += n
        upto_by_rid.append(upto)
    ratio, d, scale = worst
    print(f"[{name}] first_token_agree={first}/{len(reqs)} "
          f"compared_rows={rows}/{total} rows_by_rid={upto_by_rid} "
          f"max_abs_dlogit={d:.6g} max_abs_logit={scale:.6g} "
          f"ratio={ratio:.6g} row1_ratio_max={max(row1, default=0.0):.6g} "
          f"bound={LOGIT_BOUND if bounded else 'none'}", flush=True)
    check(first == len(reqs), f"{name}: first tokens differ")
    if bounded:
        check(min(upto_by_rid) >= MIN_ROWS,
              f"{name}: fewer than {MIN_ROWS} comparable rows")
        check(ratio <= LOGIT_BOUND,
              f"{name}: max|dlogit| {d:.6g} > {LOGIT_BOUND} x {scale:.6g}")


def decode_step_text(eng, options, npt) -> str:
    """Compiled text of the paged decode step ``eng`` runs (the same jit
    the engine builds, so the persistent cache serves it)."""
    import jax
    import jax.numpy as jnp
    from repro.serve import paging as pg
    cfg, api = eng.cfg, eng.api
    pages = jax.eval_shape(lambda: pg.init_pages(
        cfg, POOL_PAGES, api.paged_attn_layers(cfg),
        with_meta=options.policy.needs_meta, quantize=options.quantize))
    step = jax.jit(functools.partial(api.decode_step_paged, cfg=cfg,
                                     options=options, shard=None),
                   donate_argnums=(1,))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    lowered = step.lower(eng.params, pages, None, i32((N_SLOTS,)),
                         i32((N_SLOTS, npt)), i32((N_SLOTS,)),
                         jax.ShapeDtypeStruct((N_SLOTS,), jnp.bool_))
    return lowered.compile().as_text()


def one_chip(cfg, params, reqs):
    from repro.core.policy import DecodeOptions
    from repro.serve.engine import DecodeEngine
    from repro.serve.scheduler import pages_needed
    pallas = DecodeOptions(kernel_impl="pallas", budget_override=BUDGET)

    def serve(name, options, requests, sparse=True):
        eng = DecodeEngine(cfg, params, max_len=MAX_LEN, options=options)
        return eng, serve_phase(name, eng, requests, sparse=sparse)

    eng, res_pallas = serve("pallas", pallas, reqs)
    npt = max(pages_needed(len(r["tokens"]), r["max_new_tokens"],
                           cfg.gate.block_size) for r in reqs)
    t0 = time.perf_counter()
    n_kernels = decode_step_text(eng, pallas, npt).count("tpu_custom_call")
    print(f"[pallas] decode step tpu_custom_call={n_kernels} "
          f"(lower+compile {time.perf_counter() - t0:.1f}s)", flush=True)
    check(n_kernels > 0, "pallas: no Mosaic kernel in the decode step")
    del eng
    ref = pallas.replace(kernel_impl="ref")
    compare("pallas_vs_ref", serve("ref", ref, reqs)[1], res_pallas, reqs,
            bounded=False)
    del res_pallas

    # every visible block selected: the same kernels, but no selection
    # choice left for rounding to flip, so the served logits are bounded
    short = [dict(r, max_new_tokens=FULL_NEW_TOKENS) for r in reqs]
    full = pallas.replace(budget_override=MAX_LEN)
    res_full = serve("pallas_full", full, short, sparse=False)[1]
    compare("pallas_vs_ref_full", serve(
        "ref_full", full.replace(kernel_impl="ref"), short,
        sparse=False)[1], res_full, short, bounded=True)
    compare("int8_vs_pallas_full", res_full, serve(
        "int8_full", full.replace(quantize="int8"), short,
        sparse=False)[1], short, bounded=True)


def four_chips(cfg, params, reqs):
    import jax
    from repro.core.policy import DecodeOptions
    from repro.distributed.sharding import make_shard_fn
    from repro.launch.mesh import make_mesh
    from repro.serve.engine import DecodeEngine
    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, JAX sees "
          f"{len(jax.devices())}")
    pallas = DecodeOptions(kernel_impl="pallas", budget_override=BUDGET)
    res_local = serve_phase("pallas_1chip", DecodeEngine(
        cfg, params, max_len=MAX_LEN, options=pallas), reqs)
    mesh = make_mesh((1, 4), ("data", "model"))
    shard = make_shard_fn(mesh)
    sharded = pallas.replace(kernel_impl="sharded")
    with mesh:
        res_sh = serve_phase("sharded_4chip", DecodeEngine(
            cfg, params, max_len=MAX_LEN, shard=shard, options=sharded),
            reqs)
        check(res_sh["stats"]["pool_devices"] == 4,
              "sharded: the page pools do not span the 4 devices")
        compare("sharded_vs_1chip", res_local, res_sh, reqs, bounded=True)
        bitwise = all(res_sh[r["rid"]] == res_local[r["rid"]]
                      and (res_sh["logits"][r["rid"]]
                           == res_local["logits"][r["rid"]]).all()
                      for r in reqs)
        print(f"[sharded_vs_1chip] bitwise_equal={bitwise}", flush=True)
    for d in jax.devices():
        st = d.memory_stats() or {}
        print(f"[sharded_4chip] device {d.id} peak_bytes_in_use="
              f"{st.get('peak_bytes_in_use')}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the paged x sharded phase")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        import repro.configs as configs
        from repro.launch.compile_cache import enable_compile_cache
        from repro.models.registry import get_api
    except ImportError as e:
        print(f"chip_smoke: run from a checkout of the repository ({e})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devices[0].platform} "
              f"devices", file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device} compile_cache={cache_dir}", flush=True)

    t0 = time.perf_counter()
    cfg = configs.get("qwen3_0_6b")
    api = get_api(cfg)
    params = jax.jit(lambda key: api.init_params(key, cfg))(
        jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    reqs = make_requests(cfg.vocab_size)
    print(f"model: {cfg.arch_id} layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} vocab={cfg.vocab_size} "
          f"dtype={cfg.dtype} gate_block={cfg.gate.block_size} "
          f"d_gate={cfg.gate.d_gate} init_s={time.perf_counter() - t0:.1f} "
          f"prompts={[len(r['tokens']) for r in reqs]} "
          f"new={[r['max_new_tokens'] for r in reqs]}", flush=True)
    try:
        (four_chips if args.chips == 4 else one_chip)(cfg, params, reqs)
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
