"""Layout-parity suite for the head-major decode data path (ISSUE 2).

Three contracts:
  1. The fused gate-select kernel (interpret mode) agrees BITWISE (exact
     index arrays) with ``core.sparsity.select_blocks`` across
     budget/threshold × force-first/last configs.
  2. Contiguous-ref, contiguous Pallas-interpret and paged-serve decode
     agree over a 12-step rollout (same tolerance discipline as
     test_paging: float32 reduced config, <= 1e-3 logits); the sharded
     path re-runs the 12-step subprocess parity on the head-major state.
  3. The decode hot path stays transpose-free: no cache-sized
     moveaxis/swapaxes inside the decode kernels or their jnp refs, and
     a zero selection cap is an error (not a silent budget fallback).
"""
import dataclasses
import functools
import inspect
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.config import GateConfig, reduced
from repro.core import sparsity as sp
from repro.core.policy import DecodeOptions
from repro.kernels import ops
from repro.models import transformer as tf
from repro.models.common import NEG_INF
from repro.models.registry import get_api
from repro.serve.engine import DecodeEngine

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# 1. fused gate-select kernel == select_blocks, bitwise
# ---------------------------------------------------------------------------

_GS = dict(block_size=8, d_gate=16, token_budget=32)
GS_CONFIGS = [
    GateConfig(**_GS, method="budget"),
    GateConfig(**_GS, method="budget", always_first_block=False),
    GateConfig(**_GS, method="budget", always_first_block=False,
               always_last_block=False),
    GateConfig(**_GS, method="threshold", threshold=5e-3),
    GateConfig(**_GS, method="threshold", threshold=2e-2,
               always_first_block=False, always_last_block=False),
]


def _select_blocks_chain(qg, kg, n_valid, cfg):
    """The pre-fusion jnp chain the kernel replaces (scores -> visibility
    mask -> [softmax] -> select_blocks)."""
    dg = qg.shape[-1]
    scores = jnp.einsum("bhd,bhnd->bhn", qg.astype(jnp.float32),
                        kg.astype(jnp.float32)) / np.sqrt(dg)
    nb = scores.shape[-1]
    vmask = jnp.arange(nb)[None, None] < n_valid[:, None, None]
    scores = jnp.where(vmask, scores, NEG_INF)
    if cfg.method == "threshold":
        scores = jax.nn.softmax(scores, axis=-1)
    idx, _ = sp.select_blocks(scores, n_valid, cfg)
    return idx


@pytest.mark.parametrize("cfg", GS_CONFIGS,
                         ids=[f"{c.method}_ff{int(c.always_first_block)}"
                              f"_fl{int(c.always_last_block)}"
                              + (f"_tau{c.threshold:g}"
                                 if c.method == "threshold" else "")
                              for c in GS_CONFIGS])
def test_gate_select_kernel_bitwise(cfg):
    b, hkv, nb, dg = 3, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(17), 2)
    qg = jax.random.normal(ks[0], (b, hkv, dg), jnp.float32)
    kg = jax.random.normal(ks[1], (b, hkv, nb, dg), jnp.float32)
    n_valid = jnp.array([nb, 9, 1], jnp.int32)    # full / partial / 1 block
    want = np.asarray(_select_blocks_chain(qg, kg, n_valid, cfg))
    got_ref = np.asarray(ops.gate_select(qg, kg, n_valid, cfg, impl="ref"))
    got_pal = np.asarray(ops.gate_select(qg, kg, n_valid, cfg,
                                         impl="pallas_interpret"))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_pal, want)


def test_gate_select_respects_max_selected_cap():
    cfg = GS_CONFIGS[0]
    qg = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 16), jnp.float32)
    kg = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 8, 16), jnp.float32)
    nv = jnp.array([8], jnp.int32)
    for impl in ("ref", "pallas_interpret"):
        idx = ops.gate_select(qg, kg, nv, cfg, max_selected=3, impl=impl)
        assert idx.shape == (1, 1, 3)


# ---------------------------------------------------------------------------
# 2. contiguous ref / contiguous interpret-kernel / paged / sharded parity
# ---------------------------------------------------------------------------

def _tiny_cfg(method="budget"):
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    return cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=32, method=method,
        threshold=2e-2))


def _rollout(cfg, params, state, tok, step, n=12):
    """n decode steps; returns (per-step logits list, final state)."""
    lgs = []
    for _ in range(n):
        lg, state, _ = step(params, state, tok)
        lgs.append(np.asarray(lg, np.float32))
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
    return lgs, state


@pytest.mark.parametrize("method", ["budget", "threshold"])
def test_contiguous_ref_vs_interpret_12step(method):
    """Ref jnp decode vs the full Pallas path (fused gate-select + folded
    block-sparse kernel, interpret mode) over a 12-step rollout."""
    cfg = _tiny_cfg(method)
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 41), 0,
                              cfg.vocab_size)
    logits, st = api.prefill(params, {"tokens": toks}, cfg, 64)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    step_ref = jax.jit(functools.partial(
        tf.lm_decode_step, cfg=cfg, options=DecodeOptions()))
    step_pal = jax.jit(functools.partial(
        tf.lm_decode_step, cfg=cfg,
        options=DecodeOptions(kernel_impl="pallas_interpret")))
    lg_r, st_r = _rollout(cfg, params, st, tok, step_ref)
    lg_p, st_p = _rollout(cfg, params, st, tok, step_pal)
    for i, (a, b) in enumerate(zip(lg_r, lg_p)):
        d = float(np.max(np.abs(a - b)))
        assert d <= 1e-3, f"step {i}: dlogit {d}"
    for name in ("k_cache", "v_cache", "kg_cache"):
        a, b = getattr(st_r, name), getattr(st_p, name)
        d = float(jnp.max(jnp.abs(a - b)))
        assert d <= 1e-3, f"{name}: {d}"


def test_contiguous_vs_paged_12step():
    """Paged continuous-batching serve vs per-request contiguous decode,
    12 generated tokens per request, after the head-major refactor."""
    cfg = _tiny_cfg()
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    reqs = [{"rid": i, "max_new_tokens": 12,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, pl in enumerate((21, 17, 30))]
    eng = DecodeEngine(cfg, params, max_len=128)
    res = eng.serve(reqs, n_slots=2, collect_logits=True)
    assert res["stats"]["retired"] == len(reqs)
    for r in reqs:
        logits, st = api.prefill(
            params, {"tokens": jnp.asarray(r["tokens"])[None]}, cfg, 128)
        lgs = [np.asarray(logits[0], np.float32)]
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = [int(t[0])]
        for _ in range(11):
            t, lg, st, _ = eng._step(params, st, t)
            lgs.append(np.asarray(lg[0], np.float32))
            toks.append(int(t[0]))
        assert res[r["rid"]] == toks
        d = float(np.max(np.abs(res["logits"][r["rid"]] - np.stack(lgs))))
        assert d <= 1e-3, f"rid {r['rid']}: logit diff {d}"


@pytest.mark.slow
def test_sharded_layout_parity():
    """Sequence-sharded decode on the head-major state == ref, 12 steps
    (subprocess: 8 forced host devices). Non-slow coverage of the same
    helper lives in test_distributed; this pins it to the layout suite."""
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(here, "..", "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(here, "sharded_helpers.py"),
         "sharded_decode_parity"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"failed:\n{r.stdout}\n{r.stderr}"
    assert "sharded_decode_parity OK" in r.stdout


# ---------------------------------------------------------------------------
# 3. structural invariants
# ---------------------------------------------------------------------------

def test_no_cache_sized_transpose_on_decode_path():
    """The head-major invariant, enforced at the source level: no
    moveaxis/swapaxes/transpose inside the decode kernels or their refs
    (mirrors the acceptance grep; gather_kv is the documented dense-only
    exception and lives outside these functions)."""
    from repro.kernels import block_sparse_decode as bsd
    from repro.kernels import gate_select as gs
    from repro.kernels import ref
    from repro.serve.offload import OffloadedKV
    fns = (bsd.block_sparse_decode, bsd.block_sparse_decode_paged,
           ref.sparse_decode_ref, ref.paged_sparse_decode_ref,
           ref.dense_decode_ref, gs.fused_gate_select, gs.gate_select_ref,
           gs.fused_gate_select_paged, OffloadedKV.fetch)
    for fn in fns:
        src = inspect.getsource(fn)
        for tok in ("moveaxis", "swapaxes", ".transpose("):
            assert tok not in src, f"{fn.__name__} contains {tok}"


# shapes an op of the compiled paged step may not produce unless it is
# the in-place write: one layer's pool, its one-layer slice, the stack
_POOL_COPY_OPS = ("copy", "dynamic-slice", "dynamic-update-slice", "fusion")


def _hlo_computations(text):
    """Compiled HLO text -> {computation name: [instruction lines]}."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None and line.startswith(" "):
            comps[name].append(line.strip())
    return comps


def _pool_copies(text, pool_shapes):
    """Instructions of ``_POOL_COPY_OPS`` whose result has one of
    ``pool_shapes``; a fusion whose root is a scatter is the in-place
    token append into the carried pool and does not count."""
    comps = _hlo_computations(text)
    roots = {n: next((ln for ln in body if ln.startswith("ROOT ")), "")
             for n, body in comps.items()}
    found = []
    for body in comps.values():
        for ln in body:
            m = re.match(r"(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                         r"([\w\-]+)\(", ln)
            if not m or m.group(2) not in _POOL_COPY_OPS:
                continue
            shape = tuple(int(d) for d in m.group(1).split(",") if d)
            if shape not in pool_shapes:
                continue
            called = re.search(r"calls=%([\w.\-]+)", ln)
            if m.group(2) == "fusion" and called and " scatter(" in \
                    roots.get(called.group(1), ""):
                continue
            found.append(ln[:160])
    return found


@pytest.mark.parametrize("quantize", [None, "int8"], ids=["fp", "int8"])
def test_paged_step_writes_pools_in_place(quantize):
    """The compiled, donated paged decode step of the tiny bench config
    (2 slots) holds no pool-sized copy, slice, restack or fusion: the
    layer scan carries the stacked pools and every layer writes its one
    token in place. Its temp memory stays under a quarter of the pools'
    bytes. The pool has 1024 pages, so that the step's pool-independent
    temp (the per-layer weight slices, ~0.16 MB) is small beside a
    quarter of the int8 pools too; a scan over the pools as xs/ys needs
    about one more pool of temp."""
    import json
    bench = os.path.join(os.path.dirname(__file__), "..", "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness.model import decode_options, param_shapes, program_config
    from repro.serve import paging as pg
    with open(os.path.join(bench, "tests", "data", "tiny.json")) as f:
        conf = json.load(f)
    cfg = program_config(conf)
    opts = decode_options(cfg, conf).replace(quantize=quantize)
    api = get_api(cfg)
    n_pages, slots, npt = 1024, 2, 16
    n_layers = api.paged_attn_layers(cfg)
    pages = jax.eval_shape(lambda: pg.init_pages(
        cfg, n_pages, n_layers, quantize=quantize))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    step = jax.jit(functools.partial(api.decode_step_paged, cfg=cfg,
                                     options=opts, shard=None),
                   donate_argnums=(1,))
    compiled = step.lower(
        param_shapes(cfg), pages, None, i32((slots,)), i32((slots, npt)),
        i32((slots,)), jax.ShapeDtypeStruct((slots,), jnp.bool_),
        budget_blocks=i32((slots,))).compile()
    layer_pool = (n_pages, cfg.n_kv_heads, cfg.gate.block_size,
                  cfg.resolved_head_dim)
    shapes = {layer_pool, (1,) + layer_pool, (n_layers,) + layer_pool}
    assert _pool_copies(compiled.as_text(), shapes) == []
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(pages))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 4, (temp, pool_bytes)


@pytest.mark.parametrize("program", ["step", "step_budget", "step_evict",
                                     "prefill"])
def test_serve_programs_keep_their_trace_names(program):
    """The benchmark finds serve()'s programs in the device trace by name
    (``decode_step_ms`` and ``decode_hbm_share`` read
    ``jit_paged_decode_step``, ``prefill_ms`` reads ``jit_paged_prefill``):
    the engine's packed step, in each flavor serve() dispatches, and its
    bucketed prefill lower, from shapes alone, to modules of those
    names."""
    from repro.serve import paging as pg
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=16))
    api = get_api(cfg)
    params = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    eng = DecodeEngine(cfg, params, max_len=64)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    slots, npt, n_pages = 2, 4, 9
    if program == "prefill":
        lowered = eng._prefill_fn(2).lower(
            params, {"tokens": i32((1, 2 * cfg.gate.block_size)),
                     "lengths": i32((1,))})
        want = "jit_paged_prefill"
    else:
        track, use_budget = program == "step_evict", program == "step_budget"
        pages = jax.eval_shape(lambda: pg.init_pages(
            cfg, n_pages, api.paged_attn_layers(cfg),
            ghost_rows=slots * npt if track else 0))
        lead = 4 if use_budget else 3   # token, cur_len, active[, budget]
        lowered = eng._paged_step(track, use_budget).lower(
            params, pages, None, i32((slots, lead + npt)))
        want = "jit_paged_decode_step"
    assert re.match(r"module @(\S+)", lowered.as_text()).group(1) == want


def test_select_blocks_zero_cap_is_error():
    """max_selected=0 must raise, not silently fall back to the config
    budget (ISSUE 2 satellite)."""
    scores = jnp.zeros((1, 1, 8))
    nv = jnp.array([8])
    cfg = GateConfig(block_size=8, token_budget=32)
    with pytest.raises(ValueError):
        sp.select_blocks(scores, nv, cfg, max_selected=0)
    with pytest.raises(ValueError):
        sp.budget_select(scores, nv, cfg, max_selected=0)
    with pytest.raises(ValueError):
        sp.select_blocks(scores, nv,
                         dataclasses.replace(cfg, method="threshold"),
                         max_selected=-1)
    # a positive explicit cap still works and is honoured
    idx, _ = sp.select_blocks(scores, nv, cfg, max_selected=3)
    assert idx.shape[-1] == 3
