"""Serve a small model with batched requests through the sparse decode
engine — the paper's deployment scenario (long decoding of reasoning
models) end to end.

    PYTHONPATH=src python examples/serve_sparse.py [--arch qwen3_0_6b]
        [--budget 128] [--method budget|threshold] [--batch 4] [--new 64]
        [--policy gate|quest|oracle|sliding_window] [--temperature 0]
        [--top-p 1.0] [--paged]

Default: one uniform batch through ``DecodeEngine.generate``. With
``--paged``, ragged requests (mixed prompt lengths and decode budgets) go
through the continuous-batching paged-KV path (``DecodeEngine.serve``):
iteration-level admission into decode slots, per-request page tables over
a shared page pool, and the gate's K-compression cache paged alongside
the raw KV — plus PER-REQUEST overrides (one request gets a halved token
budget, applied as a runtime mask). Decode behavior is one
``DecodeOptions`` object: ``--policy`` swaps the selection strategy and
``--temperature``/``--top-p`` switch greedy to stochastic sampling.
Either way the trailing partial block is force-selected
(K-compression-cache semantics) and the engine reports MEASURED achieved
sparsity + derived I/O economics. Compiles persist in JAX's compilation
cache (``repro.launch.compile_cache``).
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

import repro.configs as configs
from repro.config import reduced
from repro.core.policy import DecodeOptions, get_policy
from repro.data.pipeline import DataState, make_batch
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import get_api
from repro.serve.engine import DecodeEngine
from repro.serve.eviction import EvictionConfig
from repro.serve.sampling import SamplingParams


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--budget", type=int, default=128)
    ap.add_argument("--method", default="budget",
                    choices=["budget", "threshold"])
    ap.add_argument("--threshold", type=float, default=4e-3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prefill", type=int, default=256)
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--policy", default="gate",
                    choices=["gate", "quest", "quest_recompute", "oracle",
                             "sliding_window"],
                    help="block-selection policy (core.policy); 'quest' "
                         "runs off the incremental metadata cache, "
                         "'quest_recompute' is the O(S) reference")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; >0 enables stochastic sampling")
    ap.add_argument("--top-p", type=float, default=1.0, dest="top_p")
    ap.add_argument("--paged", action="store_true",
                    help="ragged requests through the continuous-batching "
                         "paged-KV engine (serve) instead of one uniform "
                         "batch (generate)")
    ap.add_argument("--admission", default="lazy",
                    choices=["lazy", "reserve"],
                    help="paged admission policy: lazy allocate-on-demand "
                         "with preemption/swap (default) vs upfront "
                         "full-lifetime reservation")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="page-pool size; undersize it to watch lazy "
                         "admission preempt+swap instead of stalling")
    ap.add_argument("--eviction", action="store_true",
                    help="with --paged and an undersized --pool-pages: "
                         "evict cold pages (RaaS victim model, ghost-row "
                         "metadata, optimistic replay on re-touch) before "
                         "falling back to whole-request preemption")
    ap.add_argument("--quantize", default=None, choices=["int8"],
                    help="with --paged: int8 K/V page pools with per-"
                         "(page, head) scales and dequant fused into the "
                         "block-sparse kernels — ~4x smaller pool and "
                         "swap traffic at decode-realistic accuracy "
                         "(see docs/ARCHITECTURE.md section 8)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(configs.get(args.arch))
    if not (cfg.gate.enabled and cfg.has_attention and cfg.is_decoder):
        raise SystemExit(f"{args.arch}: no decode gate (family {cfg.family}) "
                         "— pick a gated arch for this example")
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=16, d_gate=16, method=args.method,
        token_budget=args.budget, threshold=args.threshold))

    params = get_api(cfg).init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prefill + args.new + 16
    if args.quantize and not args.paged:
        raise SystemExit("--quantize needs --paged (pools are paged-only)")
    opts = DecodeOptions(
        policy=get_policy(args.policy),
        quantize=args.quantize,
        sampling=SamplingParams(temperature=args.temperature,
                                top_p=args.top_p))

    if args.paged:
        rng = np.random.default_rng(3)
        reqs = []
        for i in range(args.batch):
            plen = int(rng.integers(max(args.prefill // 4, 1),
                                    args.prefill + 1))
            mn = int(rng.integers(max(args.new // 4, 1), args.new + 1))
            reqs.append({"rid": i, "max_new_tokens": mn,
                         "tokens": rng.integers(
                             0, cfg.vocab_size, size=(plen,)).astype(np.int32)})
        # per-request overrides ride in the request dict: request 0 runs at
        # HALF the token budget (runtime mask — same compiled step)
        reqs[0]["budget"] = max(cfg.gate.block_size, args.budget // 2)
        eng = DecodeEngine(cfg, params, max_len=max_len, options=opts)
        ev = EvictionConfig() if args.eviction else None
        t0 = time.perf_counter()
        res = eng.serve(reqs, n_slots=max(2, args.batch // 2),
                        num_pages=args.pool_pages, admission=args.admission,
                        eviction=ev)
        wall = time.perf_counter() - t0
        st = res["stats"]
        print(f"arch={cfg.arch_id} policy={args.policy} paged serve "
              f"(admission={args.admission}): {len(reqs)} ragged requests, "
              f"{st['generated_tokens']} tokens in {st['decode_steps']} steps "
              f"({st['tok_per_s']:.1f} tok/s, wall {wall:.2f}s)")
        print(f"slot utilisation {st['slot_util']:.2f} "
              f"(mean active {st['mean_active_slots']:.2f}), "
              f"page pool {st['num_pages']} x {st['page_size']} tokens "
              f"(peak used {st['peak_pages_used']}), "
              f"admission stalls {st['admission_stalls']}, "
              f"preemptions {st['preemptions']} "
              f"({st['retired_preempted']} requests finished after a swap)")
        if args.eviction:
            print(f"eviction: {st['evictions']} pages evicted, "
                  f"{st['page_restores']} restored on re-touch, "
                  f"{st['replay_steps']} replayed steps, "
                  f"swap peak {st['swap']['peak_host_bytes']} host bytes")
        print("measured sparsity by request (req 0 at half budget): "
              + ", ".join(f"{rid}: {rho:.3f}" for rid, rho in
                          sorted(st["sparsity_by_rid"].items())))
        for r in reqs[:2]:
            print(f"req{r['rid']} ({len(r['tokens'])} prompt tok): "
                  f"{res[r['rid']][:12]}")
        return

    # batched requests (shared-length packing; ragged lengths via kv_len)
    batch = {"tokens": make_batch(cfg, args.batch, args.prefill,
                                  DataState(3, 0))["tokens"]}

    eng = DecodeEngine(cfg, params, max_len=max_len, options=opts)
    t0 = time.perf_counter()
    res = eng.generate(batch, args.new)
    wall = time.perf_counter() - t0
    stats = eng.sparsity_stats()           # measured over the decode above

    print(f"arch={cfg.arch_id} policy={args.policy} method={args.method} "
          f"budget={args.budget} batch={args.batch}")
    print(f"prefill {args.prefill} tok: {res['prefill_s'] * 1e3:.1f} ms; "
          f"decode {args.new} steps: {res['decode_s'] * 1e3:.1f} ms "
          f"({res['tok_per_s']:.1f} tok/s, wall {wall:.2f}s)")
    print(f"achieved block sparsity: {stats['sparsity']:.3f} "
          f"(derived KV I/O speedup {stats['io_speedup']:.2f}x, "
          f"gate overhead {stats['gate_overhead_frac'] * 100:.2f}% of KV read)")
    toks = np.asarray(res["tokens"])
    print(f"generated tokens [req0, first 16]: {toks[0, :16].tolist()}")


if __name__ == "__main__":
    main()
