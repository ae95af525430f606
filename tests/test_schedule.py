"""Step-level selection plan (ISSUE 6): SelectionSchedule staging,
cross-layer plan reuse, cross-head unification — plus the selection-cap /
telemetry bugfix regressions that rode along.

Coverage:
  1. SelectionSchedule / DecodeOptions validation and stage derivation.
  2. Reuse-parity: the dynamic (plan-carrying) machinery with an
     all-select schedule is BITWISE equal to the committed goldens on the
     contiguous and paged paths (the sharded twin lives in
     sharded_helpers.paged_sharded_schedule_parity); reuse + correction
     schedules are deterministic under preempt -> swap -> resume.
  3. unify_heads returns identical rows for every KV head, on every
     scoring policy.
  4. Bugfix regressions: threshold_select's telemetry mask vs the capped
     index list (admitted > cap); SlidingWindowPolicy on a
     non-block-aligned cache; DecodeOptions.max_selected ceil rounding.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import capture_golden_policy as G
from repro.core import attngate as ag
from repro.core import policy as pol
from repro.core import sparsity as sp
from repro.core.policy import (STAGE_DENSE, STAGE_REUSE, STAGE_SELECT,
                               DecodeOptions, DensePolicy, GatePolicy,
                               OraclePolicy, QuestRecomputePolicy,
                               SelectionInputs, SelectionSchedule,
                               SlidingWindowPolicy, selection_width)
from repro.models.registry import get_api
from repro.serve.engine import DecodeEngine

jax.config.update("jax_platform_name", "cpu")

HERE = os.path.dirname(__file__)
GOLD = np.load(os.path.join(HERE, "golden_policy.npz"))


def _params_and_prompt(cfg):
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(G.PARAM_SEED), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(G.PROMPT_SEED),
                              G.PROMPT_SHAPE, 0, cfg.vocab_size)
    return api, params, toks


def _contiguous_rollout(cfg, params, toks, options):
    eng = DecodeEngine(cfg, params, max_len=G.MAX_LEN, options=options)
    tok, st = eng.prefill({"tokens": toks})
    lgs, tks = [], []
    for _ in range(G.N_STEPS):
        tok, lg, st = eng._step(params, st, tok)[:3]
        lgs.append(np.asarray(lg, np.float32))
        tks.append(np.asarray(tok, np.int32))
    return np.stack(lgs), np.stack(tks)


# ---------------------------------------------------------------------------
# 1. schedule validation + staging
# ---------------------------------------------------------------------------

def test_selection_schedule_validation():
    assert SelectionSchedule().is_trivial
    assert not SelectionSchedule().needs_plan
    # unify alone: non-trivial but no plan carried (every layer selects)
    s = SelectionSchedule(unify_heads=True)
    assert not s.is_trivial and not s.needs_plan
    assert SelectionSchedule(select_layer=0).needs_plan
    assert SelectionSchedule(dense_first_n=1).needs_plan
    with pytest.raises(ValueError):
        SelectionSchedule(dense_first_n=-1)
    with pytest.raises(ValueError):                     # correction w/o plan
        SelectionSchedule(correction_layers=(3,))
    with pytest.raises(ValueError):                     # select inside dense
        SelectionSchedule(dense_first_n=2, select_layer=1)
    with pytest.raises(ValueError):                     # unsorted / dup
        SelectionSchedule(select_layer=0, correction_layers=(3, 2))
    with pytest.raises(ValueError):                     # correction <= select
        SelectionSchedule(select_layer=2, correction_layers=(2,))


def test_layer_stages_derivation():
    s = SelectionSchedule(dense_first_n=1, select_layer=2,
                          correction_layers=(4,))
    assert s.layer_stages(6) == (STAGE_DENSE, STAGE_DENSE, STAGE_SELECT,
                                 STAGE_REUSE, STAGE_SELECT, STAGE_REUSE)
    # select_layer=None: every layer past the dense prefix selects
    assert SelectionSchedule(dense_first_n=1).layer_stages(3) == \
        (STAGE_DENSE, STAGE_SELECT, STAGE_SELECT)
    with pytest.raises(ValueError):                     # all-dense stack
        SelectionSchedule(dense_first_n=3).layer_stages(3)
    with pytest.raises(ValueError):                     # out of range
        SelectionSchedule(select_layer=4).layer_stages(3)
    with pytest.raises(ValueError):
        SelectionSchedule(select_layer=0,
                          correction_layers=(5,)).layer_stages(3)


def test_decode_options_schedule_validation():
    sched = SelectionSchedule(select_layer=0, correction_layers=(1,))
    o = DecodeOptions(schedule=sched)
    assert hash(o) == hash(DecodeOptions(schedule=sched))  # jit-static
    with pytest.raises(ValueError):                     # dense has no plan
        DecodeOptions(policy=DensePolicy(), schedule=sched)
    # sharded: reuse-only (the shard body always runs sparse attention)
    DecodeOptions(kernel_impl="sharded", schedule=sched)
    for bad in (SelectionSchedule(dense_first_n=1, select_layer=1),
                SelectionSchedule(select_layer=1),
                SelectionSchedule(unify_heads=True)):
        with pytest.raises(ValueError):
            DecodeOptions(kernel_impl="sharded", schedule=bad)


def test_max_selected_ceil():
    """Bugfix: a budget_override that is not a block multiple rounds UP —
    a 100-token override at block 64 buys 2 blocks (128 tokens), never 1
    (64 tokens, silently under-delivering)."""
    cfg = G.tiny_cfg("budget").replace(
        gate=dataclasses.replace(G.tiny_cfg("budget").gate, block_size=64))
    assert DecodeOptions(budget_override=100).max_selected(cfg) == 2
    assert DecodeOptions(budget_override=64).max_selected(cfg) == 1
    assert DecodeOptions(budget_override=1).max_selected(cfg) == 1
    # the CONFIG path keeps floor on purpose (paper §3.1 k = budget // bs)
    assert sp.resolve_max_selected(dataclasses.replace(
        cfg.gate, block_size=64, token_budget=100)) == 1


# ---------------------------------------------------------------------------
# 2. reuse parity
# ---------------------------------------------------------------------------

def test_all_select_schedule_contiguous_bitwise_golden():
    """The plan-carrying machinery (lax.cond staging, carried plan, gated
    Kg advance) with an every-layer-selects schedule reproduces the
    committed golden trajectory BITWISE on the contiguous path."""
    G.check_versions(GOLD)
    cfg = G.tiny_cfg("budget")
    _, params, toks = _params_and_prompt(cfg)
    sched = SelectionSchedule(
        select_layer=0, correction_layers=tuple(range(1, cfg.num_layers)))
    lgs, tks = _contiguous_rollout(cfg, params, toks,
                                   DecodeOptions(schedule=sched))
    np.testing.assert_array_equal(tks, GOLD["ct_budget_tokens"])
    np.testing.assert_array_equal(lgs, GOLD["ct_budget_logits"])


def test_all_select_schedule_paged_bitwise_golden():
    G.check_versions(GOLD)
    cfg = G.tiny_cfg("budget")
    _, params, _ = _params_and_prompt(cfg)
    sched = SelectionSchedule(
        select_layer=0, correction_layers=tuple(range(1, cfg.num_layers)))
    eng = DecodeEngine(cfg, params, max_len=128,
                       options=DecodeOptions(schedule=sched))
    res = eng.serve(G.paged_requests(cfg), n_slots=2, collect_logits=True)
    for rid in range(len(G.PAGED_SPECS)):
        np.testing.assert_array_equal(
            np.asarray(res[rid], np.int32), GOLD[f"paged_rid{rid}_tokens"])
        np.testing.assert_array_equal(
            res["logits"][rid], GOLD[f"paged_rid{rid}_logits"])


def test_paged_sharded_schedule_parity_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "sharded_helpers.py"),
         "paged_sharded_schedule_parity"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"failed:\n{r.stdout}\n{r.stderr}"
    assert "paged_sharded_schedule_parity OK" in r.stdout


def test_reuse_schedule_deterministic_under_preemption():
    """A reuse + correction schedule must resume bitwise-identically after
    preempt -> host swap -> re-admission: the plan is rebuilt from the
    select layer every step (never persisted), and the selecting layers'
    Kg page rows ride the swap like any other page bytes."""
    cfg = G.tiny_cfg("budget")
    _, params, _ = _params_and_prompt(cfg)
    sched = SelectionSchedule(select_layer=0, correction_layers=())
    eng = DecodeEngine(cfg, params, max_len=128,
                       options=DecodeOptions(schedule=sched))
    ample = eng.serve(G.paged_requests(cfg), n_slots=2, collect_logits=True)
    tight = eng.serve(G.paged_requests(cfg), n_slots=3, num_pages=12,
                      collect_logits=True)
    assert tight["stats"]["preemptions"] > 0, tight["stats"]
    for rid in range(len(G.PAGED_SPECS)):
        assert tight[rid] == ample[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(tight["logits"][rid],
                                      ample["logits"][rid])


def test_reuse_schedule_changes_and_dense_prefix_runs():
    """Sanity on the non-trivial schedules: reuse produces a different
    (but finite) trajectory than per-layer selection, and a dense prefix +
    unify_heads schedule traces and runs on both decode paths."""
    cfg = G.tiny_cfg("budget")
    _, params, toks = _params_and_prompt(cfg)
    base, _ = _contiguous_rollout(cfg, params, toks, DecodeOptions())
    reuse, _ = _contiguous_rollout(
        cfg, params, toks,
        DecodeOptions(schedule=SelectionSchedule(select_layer=0)))
    assert np.isfinite(reuse).all()
    assert not np.array_equal(base, reuse)
    mix, _ = _contiguous_rollout(
        cfg, params, toks,
        DecodeOptions(schedule=SelectionSchedule(
            dense_first_n=1, select_layer=1, unify_heads=True)))
    assert np.isfinite(mix).all()
    eng = DecodeEngine(cfg, params, max_len=128,
                       options=DecodeOptions(schedule=SelectionSchedule(
                           dense_first_n=1, select_layer=1)))
    res = eng.serve(G.paged_requests(cfg), n_slots=2, collect_logits=True)
    for rid in range(len(G.PAGED_SPECS)):
        assert np.isfinite(res["logits"][rid]).all()


# ---------------------------------------------------------------------------
# 3. unify_heads
# ---------------------------------------------------------------------------

def _unify_inputs(cfg, needs_gate):
    b, hkv, g, dh = 2, cfg.n_kv_heads, cfg.gqa_group, cfg.resolved_head_dim
    bs = cfg.gate.block_size
    nb = 6
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (b, 1, hkv * g, dh), jnp.float32)
    k_cache = jax.random.normal(ks[1], (b, hkv, nb * bs, dh), jnp.float32)
    kg = jax.random.normal(ks[2], (b, hkv, nb, cfg.gate.d_gate), jnp.float32)
    gate = ag.init_attngate(ks[3], n_kv_heads=hkv, group=g, head_dim=dh,
                            cfg=cfg.gate, dtype="float32") if needs_gate \
        else None
    new_len = jnp.array([nb * bs, nb * bs - 3], jnp.int32)
    return SelectionInputs(q_nope=q, qr=q, pos=new_len[:, None] - 1,
                           new_len=new_len, gate_params=gate, kg=kg,
                           k_cache=k_cache)


@pytest.mark.parametrize("policy", [GatePolicy(), QuestRecomputePolicy(),
                                    OraclePolicy()])
def test_unify_heads_identical_rows(policy):
    cfg = G.tiny_cfg("budget")
    inp = _unify_inputs(cfg, policy.needs_gate)
    idx = np.asarray(policy.select(inp, cfg, unify_heads=True))
    assert idx.shape[1] == cfg.n_kv_heads
    for h in range(1, idx.shape[1]):
        np.testing.assert_array_equal(idx[:, h], idx[:, 0])
    # and it actually selected something
    assert (idx >= 0).any()
    # per-head selection (the default) is allowed to disagree across heads
    per_head = np.asarray(policy.select(inp, cfg, unify_heads=False))
    assert per_head.shape == idx.shape


def test_unify_heads_threshold_gate():
    cfg = G.tiny_cfg("threshold")
    inp = _unify_inputs(cfg, True)
    idx = np.asarray(GatePolicy().select(inp, cfg, unify_heads=True))
    for h in range(1, idx.shape[1]):
        np.testing.assert_array_equal(idx[:, h], idx[:, 0])


def test_selection_width_matches_policies():
    """The plan buffer a schedule carries must always shape-match a fresh
    selection — widths mirrored for every policy/method/cap combination."""
    cfg = G.tiny_cfg("budget")
    nb = 8
    inp = _unify_inputs(cfg, True)      # 6 blocks, but widths use nb
    for policy in (GatePolicy(), QuestRecomputePolicy(), OraclePolicy(),
                   SlidingWindowPolicy()):
        for ms in (None, 2, 100):
            w = selection_width(policy, cfg, nb, ms)
            idx = policy.select(inp, cfg, max_selected=ms)
            assert idx.shape[-1] == selection_width(policy, cfg, 6, ms), \
                (type(policy).__name__, ms)
            assert w >= 1
    tcfg = G.tiny_cfg("threshold")
    tinp = _unify_inputs(tcfg, True)
    idx = GatePolicy().select(tinp, tcfg, max_selected=100)
    assert idx.shape[-1] == selection_width(GatePolicy(), tcfg, 6, 100)


# ---------------------------------------------------------------------------
# 4. bugfix regressions
# ---------------------------------------------------------------------------

def test_threshold_mask_matches_capped_idx():
    """Bugfix: when the threshold admits MORE blocks than ``max_selected``,
    the telemetry mask must describe the capped list the kernel attends —
    not every admitted block (which overstated density)."""
    from repro.config import GateConfig
    cfg = GateConfig(block_size=8, method="threshold", threshold=0.01,
                     always_first_block=False, always_last_block=False)
    nb, cap = 8, 3
    # all 8 blocks clear the threshold; only the top 3 may be attended
    probs = jnp.tile(jnp.linspace(0.2, 0.9, nb)[None, None, :], (2, 2, 1))
    n_valid = jnp.array([nb, nb], jnp.int32)
    idx, mask = sp.threshold_select(probs, n_valid, cfg, cap)
    assert int(jnp.sum(idx >= 0, axis=-1).max()) == cap
    # the mask is exactly the scatter of the capped winners
    np.testing.assert_array_equal(
        np.asarray(jnp.sum(mask, -1)), np.full((2, 2), cap))
    sel = np.sort(np.asarray(idx), axis=-1)[..., -cap:]
    for bi in range(2):
        for h in range(2):
            assert set(np.flatnonzero(np.asarray(mask)[bi, h])) == \
                set(sel[bi, h].tolist())
    # measured sparsity now reflects the cap: 3 of 8 blocks -> rho = 5/8
    rho = float(sp.sparsity_ratio(mask, n_valid))
    assert abs(rho - (1 - cap / nb)) < 1e-6


def test_sliding_window_non_aligned_cache():
    """Bugfix: on a cache whose seq dim is not a multiple of block_size,
    visible_blocks (CEIL) can exceed the view's block count (FLOOR) — the
    trailing block id must be clamped into the view, same rule as
    quest.build_quest_meta (PR 5)."""
    cfg = G.tiny_cfg("budget")
    bs = cfg.gate.block_size                              # 8
    hkv, g, dh = cfg.n_kv_heads, cfg.gqa_group, cfg.resolved_head_dim
    nb = 2
    S = nb * bs + 4                                       # NOT block-aligned
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    q = jax.random.normal(ks[0], (1, 1, hkv * g, dh), jnp.float32)
    k_cache = jax.random.normal(ks[1], (1, hkv, S, dh), jnp.float32)
    new_len = jnp.array([nb * bs + 1], jnp.int32)   # ceil -> 3 > nb == 2
    inp = SelectionInputs(q_nope=q, qr=q, pos=new_len[:, None] - 1,
                          new_len=new_len, k_cache=k_cache)
    idx = np.asarray(SlidingWindowPolicy().select(inp, cfg))
    assert (idx < nb).all(), idx          # never beyond the view
    assert (idx >= -1).all()
    # the trailing slot still points at the LAST in-view block
    assert (idx[:, :, 0] == nb - 1).all(), idx


def test_engine_slot_cap_ceils():
    """Bugfix twin of max_selected: serve()'s per-request "budget" cap
    rounds UP to blocks (20 tokens @ block 8 -> 3 blocks, not 2)."""
    cfg = G.tiny_cfg("budget")
    _, params, _ = _params_and_prompt(cfg)
    eng = DecodeEngine(cfg, params, max_len=128)
    reqs = G.paged_requests(cfg)
    for r in reqs:
        r["budget"] = 20                 # ceil(20/8)=3 vs floor 2
    res = eng.serve(reqs, n_slots=2)
    by_rid = res["stats"]["sel_blocks_by_rid"]
    for rid in range(len(G.PAGED_SPECS)):
        assert by_rid[rid] <= 3.0 + 1e-6
    # a request asking for 17..24 tokens can now reach 3 blocks; with the
    # old floor its cap was 2 — detectable whenever the policy wants >2
    assert max(by_rid.values()) > 2.0, by_rid
