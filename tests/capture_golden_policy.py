"""Capture golden decode trajectories for tests/golden_policy.npz.

The committed npz was produced by running this script against the
PRE-DecodeOptions tree (the old ``sparse``/``sparse_impl`` kwarg API),
one commit before the policy redesign landed — tests/test_policy.py
replays the same workloads through DecodeOptions and asserts BITWISE
equality, proving the refactor behavior-preserving. The script itself
tracks the current API so the fixture stays regenerable: if a future PR
intentionally changes decode numerics (layout change, kernel rewrite),
run both capture modes on the pre-change tree (or accept the new
numerics by running on the post-change tree) and commit the refreshed
npz alongside an explanation.

Usage (from repo root):
    PYTHONPATH=src:tests python tests/capture_golden_policy.py contiguous_paged
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src:tests python tests/capture_golden_policy.py sharded

Both modes merge their arrays into tests/golden_policy.npz. The two modes
are separate processes because jax pins the device count at first init.

Refresh history: the paged_rid* arrays were recaptured for ISSUE 5's
serve-path prefill BUCKETING (prompts right-padded to power-of-two page
buckets): the padded prefill changes XLA's fp reduction order, moving
paged logits by <= 2.4e-7 while every TOKEN trajectory and the
contiguous/sharded arrays stayed bit-identical. ISSUE 6's
``DecodeOptions.max_selected`` rounding change (budget overrides now CEIL
to blocks instead of floor) moved NO goldens: every golden workload uses
the config ``token_budget`` (which keeps the paper's floor semantics via
``resolve_max_selected``), never a runtime ``budget_override`` — both
capture modes re-verified bitwise after the change.

jax 0.9 turned ``jax_threefry_partitionable`` on by default, which draws
different parameters from the same PRNG key, so every token array
drifted. This module pins the flag on (jax's own default, so importing it
changes nothing for other tests in the same process) and the npz was
recaptured under it; with the flag off the old token arrays still matched
and the logits differed by <= 5.7e-7 (3.9e-3 on the bf16 sharded run).
The npz also records the jax/jaxlib versions it was captured with:
``check_versions`` fails a golden test run under other versions, naming
both and the recapture command, since the numerics are only pinned for
one toolchain.

``--verify`` (the CI golden-drift guard, ISSUE 4): recompute the mode's
arrays and BITWISE-compare them against the committed npz instead of
writing — exits non-zero on drift, so a stale golden is caught as its own
CI step rather than as a confusing bitwise-test failure later:
    python tests/capture_golden_policy.py --verify contiguous_paged
"""
import dataclasses
import os
import sys

import jax
import numpy as np

# the PRNG stream the npz was captured with (jax 0.9's default)
jax.config.update("jax_threefry_partitionable", True)

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "golden_policy.npz")

# workload constants shared with tests/test_policy.py
PROMPT_SHAPE = (2, 41)          # contiguous rollouts
PROMPT_SEED = 1
PARAM_SEED = 0
N_STEPS = 12
MAX_LEN = 64
PAGED_SPECS = ((21, 12), (17, 12), (30, 12))   # (prompt_len, max_new)
PAGED_SEED = 4
SHARDED_B, SHARDED_PRE, SHARDED_MAX = 4, 120, 256


def tiny_cfg(method="budget"):
    import repro.configs as configs
    from repro.config import reduced
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    return cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=32, method=method,
        threshold=2e-2))


def sharded_cfg():
    import repro.configs as configs
    from repro.config import reduced
    cfg = reduced(configs.get("qwen3_0_6b"))
    return cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=64,
        local_cap_factor=8.0))


def paged_requests(cfg):
    rng = np.random.default_rng(PAGED_SEED)
    return [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(PAGED_SPECS)]


VERIFY = False
VERSION_KEYS = ("jax_version", "jaxlib_version")
RECAPTURE = ("PYTHONPATH=src:tests python tests/capture_golden_policy.py "
             "contiguous_paged && XLA_FLAGS=--xla_force_host_platform_"
             "device_count=8 PYTHONPATH=src:tests python "
             "tests/capture_golden_policy.py sharded")


def versions():
    import jaxlib
    return {"jax_version": jax.__version__,
            "jaxlib_version": jaxlib.__version__}


def check_versions(gold):
    """Fail unless the running jax/jaxlib are the ones ``gold`` was
    captured with: bitwise numerics are pinned for one toolchain only."""
    want = {k: str(gold[k]) if k in gold else "unrecorded"
            for k in VERSION_KEYS}
    have = versions()
    if want != have:
        raise AssertionError(
            f"golden_policy.npz was captured with {want}, this run has "
            f"{have}; if the arrays drift only by toolchain, recapture "
            f"both modes: {RECAPTURE}")


def _merge_save(arrays):
    if VERIFY:
        return _verify(arrays)
    arrays = {**arrays, **{k: np.array(v) for k, v in versions().items()}}
    if os.path.exists(OUT):
        prev = dict(np.load(OUT))
        prev.update(arrays)
        arrays = prev
    np.savez_compressed(OUT, **arrays)
    print(f"wrote {OUT}: {sorted(arrays)}")


def _verify(arrays):
    """Bitwise-compare freshly captured arrays against the committed npz."""
    gold = dict(np.load(OUT))
    check_versions(gold)
    bad = []
    for k, v in sorted(arrays.items()):
        if k not in gold:
            bad.append(f"{k}: missing from {OUT} (capture was never run?)")
        elif gold[k].shape != v.shape:
            bad.append(f"{k}: shape {gold[k].shape} != fresh {v.shape}")
        elif not np.array_equal(gold[k], v):
            d = float(np.max(np.abs(gold[k].astype(np.float64)
                                    - v.astype(np.float64))))
            bad.append(f"{k}: DRIFT (max abs diff {d:.3e})")
    if bad:
        print(f"golden drift against {OUT}:")
        for line in bad:
            print(f"  {line}")
        print("If the numerics change is intentional, re-run capture "
              "(both modes) and commit the refreshed npz with the reason.")
        sys.exit(1)
    print(f"verify OK: {sorted(arrays)} bitwise-match {OUT}")


def capture_contiguous_paged():
    jax.config.update("jax_platform_name", "cpu")
    from repro.models.registry import get_api
    from repro.serve.engine import DecodeEngine

    out = {}
    for method in ("budget", "threshold"):
        cfg = tiny_cfg(method)
        api = get_api(cfg)
        params = api.init_params(jax.random.PRNGKey(PARAM_SEED), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(PROMPT_SEED),
                                  PROMPT_SHAPE, 0, cfg.vocab_size)
        eng = DecodeEngine(cfg, params, max_len=MAX_LEN)
        tok, st = eng.prefill({"tokens": toks})
        lgs, tks = [], []
        for _ in range(N_STEPS):
            tok, lg, st = eng._step(params, st, tok)[:3]
            lgs.append(np.asarray(lg, np.float32))
            tks.append(np.asarray(tok, np.int32))
        out[f"ct_{method}_logits"] = np.stack(lgs)
        out[f"ct_{method}_tokens"] = np.stack(tks)

    cfg = tiny_cfg("budget")
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(PARAM_SEED), cfg)
    eng = DecodeEngine(cfg, params, max_len=128)
    res = eng.serve(paged_requests(cfg), n_slots=2, collect_logits=True)
    for rid in range(len(PAGED_SPECS)):
        out[f"paged_rid{rid}_logits"] = res["logits"][rid]
        out[f"paged_rid{rid}_tokens"] = np.asarray(res[rid], np.int32)
    _merge_save(out)


def capture_sharded():
    import functools
    import jax.numpy as jnp
    from repro.data.pipeline import DataState, make_batch
    from repro.models import transformer as tf
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = sharded_cfg()
    params = tf.init_lm(jax.random.PRNGKey(PARAM_SEED), cfg)
    batch = {"tokens": make_batch(cfg, SHARDED_B, SHARDED_PRE,
                                  DataState(0, 0))["tokens"]}
    logits, st = tf.lm_prefill(params, batch, cfg, max_len=SHARDED_MAX)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    shard = shd.make_shard_fn(mesh)
    from repro.core.policy import DecodeOptions
    lgs, tks = [], []
    with mesh:
        step = jax.jit(functools.partial(
            tf.lm_decode_step, cfg=cfg,
            options=DecodeOptions(kernel_impl="sharded"), shard=shard))
        for _ in range(N_STEPS):
            lg, st = step(params, st, tok)[:2]
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            lgs.append(np.asarray(lg, np.float32))
            tks.append(np.asarray(tok, np.int32))
    _merge_save({"sharded_logits": np.stack(lgs),
                 "sharded_tokens": np.stack(tks)})


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if a != "--verify"]
    VERIFY = "--verify" in sys.argv[1:]
    {"contiguous_paged": capture_contiguous_paged,
     "sharded": capture_sharded}[args[0]]()
