"""DecodeOptions / selection-policy API suite (ISSUE 3).

Contracts:
  1. GatePolicy through DecodeOptions is BITWISE equal to the
     pre-refactor decode trajectories (tests/golden_policy.npz, captured
     from the old sparse/sparse_impl kwarg API before the redesign) on
     the contiguous, paged and sharded paths — the refactor is
     behavior-preserving by construction.
  2. Quest / Oracle / SlidingWindow policies satisfy shape + causality
     properties (never select an invisible block; honor the budget;
     OraclePolicy at full budget == dense logits).
  3. Sampling: top-p/top-k/temperature determinism under a fixed key,
     nucleus support restriction, greedy == argmax bitwise.
  4. serve(): per-request budget overrides are honored (measured
     selection telemetry) and per-request sampling params sample
     deterministically per seed.
  5. DecodeOptions is hashable/jit-static and validates its fields.
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
from repro.config import GateConfig
from repro.core import policy as pol
from repro.core.policy import (DecodeOptions, DensePolicy, GatePolicy,
                               OraclePolicy, QuestPolicy,
                               SlidingWindowPolicy, default_options)
from repro.models.registry import get_api
from repro.serve import sampling as smp
from repro.serve.engine import DecodeEngine
from repro.serve.sampling import SamplingParams

jax.config.update("jax_platform_name", "cpu")

HERE = os.path.dirname(__file__)
GOLD = np.load(os.path.join(HERE, "golden_policy.npz"))


def _params_and_prompt(cfg):
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(G.PARAM_SEED), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(G.PROMPT_SEED),
                              G.PROMPT_SHAPE, 0, cfg.vocab_size)
    return api, params, toks


# ---------------------------------------------------------------------------
# 1. GatePolicy == pre-refactor trajectories, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["budget", "threshold"])
def test_gate_policy_contiguous_bitwise_golden(method):
    G.check_versions(GOLD)
    cfg = G.tiny_cfg(method)
    api, params, toks = _params_and_prompt(cfg)
    eng = DecodeEngine(cfg, params, max_len=G.MAX_LEN)
    assert eng.options == DecodeOptions()        # default = gate policy
    tok, st = eng.prefill({"tokens": toks})
    lgs, tks = [], []
    for _ in range(G.N_STEPS):
        tok, lg, st, _ = eng._step(params, st, tok)
        lgs.append(np.asarray(lg, np.float32))
        tks.append(np.asarray(tok, np.int32))
    np.testing.assert_array_equal(np.stack(tks), GOLD[f"ct_{method}_tokens"])
    np.testing.assert_array_equal(np.stack(lgs), GOLD[f"ct_{method}_logits"])


def test_gate_policy_paged_bitwise_golden():
    G.check_versions(GOLD)
    cfg = G.tiny_cfg("budget")
    api, params, _ = _params_and_prompt(cfg)
    eng = DecodeEngine(cfg, params, max_len=128)
    res = eng.serve(G.paged_requests(cfg), n_slots=2, collect_logits=True)
    for rid in range(len(G.PAGED_SPECS)):
        np.testing.assert_array_equal(
            np.asarray(res[rid], np.int32), GOLD[f"paged_rid{rid}_tokens"])
        np.testing.assert_array_equal(
            res["logits"][rid], GOLD[f"paged_rid{rid}_logits"])


def test_gate_policy_sharded_bitwise_golden():
    G.check_versions(GOLD)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "sharded_helpers.py"),
         "sharded_policy_golden"],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"failed:\n{r.stdout}\n{r.stderr}"
    assert "sharded_policy_golden OK" in r.stdout


def test_paged_gate_select_kernel_matches_ref():
    """The zero-gather paged gate-select kernel (interpret mode) agrees
    BITWISE with the gather-based jnp spec, scrambled page tables."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    s, hkv, npt, dg, pool = 3, 2, 6, 16, 11
    for cfg in (GateConfig(block_size=8, d_gate=dg, token_budget=32),
                GateConfig(block_size=8, d_gate=dg, token_budget=32,
                           method="threshold", threshold=5e-3)):
        qg = jnp.asarray(rng.normal(size=(s, hkv, dg)), jnp.float32)
        kg_pages = jnp.asarray(rng.normal(size=(pool, hkv, dg)), jnp.float32)
        table = np.zeros((s, npt), np.int32)
        for i in range(s):
            table[i] = rng.choice(np.arange(1, pool), npt, replace=False)
        table = jnp.asarray(table)
        nv = jnp.array([npt, 3, 1], jnp.int32)
        want = ops.gate_select_paged(qg, kg_pages, table, nv, cfg, impl="ref")
        got = ops.gate_select_paged(qg, kg_pages, table, nv, cfg,
                                    impl="pallas_interpret")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # and == the contiguous kernel on the gathered view
        kg = jnp.swapaxes(kg_pages[table], 1, 2)
        ct = ops.gate_select(qg, kg, nv, cfg, impl="ref")
        np.testing.assert_array_equal(np.asarray(want), np.asarray(ct))


# ---------------------------------------------------------------------------
# 2. alternative policies: shape + causality + quality properties
# ---------------------------------------------------------------------------

def _decode_with(cfg, policy, n=6):
    api, params, toks = _params_and_prompt(cfg)
    eng = DecodeEngine(cfg, params, max_len=G.MAX_LEN,
                       options=DecodeOptions(policy=policy))
    tok, st = eng.prefill({"tokens": toks})
    lgs = []
    for _ in range(n):
        tok, lg, st, aux = eng._step(params, st, tok)
        eng._last_aux = aux
        lgs.append(np.asarray(lg, np.float32))
    return eng, np.stack(lgs)


@pytest.mark.parametrize("policy", [QuestPolicy(), OraclePolicy(),
                                    SlidingWindowPolicy()],
                         ids=["quest", "oracle", "sliding_window"])
def test_policy_select_shape_and_causality(policy):
    """Direct select() contract: [B,Hkv,k] int32, every non-padding id a
    VISIBLE block (< ceil(new_len/bs)), no duplicates, budget respected."""
    cfg = G.tiny_cfg()
    bs = cfg.gate.block_size
    b, hkv, s_max, dh = 2, cfg.n_kv_heads, 64, cfg.resolved_head_dim
    h = cfg.n_heads
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    new_len = jnp.array([41, 17], jnp.int32)
    inp = pol.SelectionInputs(
        q_nope=jax.random.normal(ks[0], (b, 1, h, dh), jnp.float32),
        qr=jax.random.normal(ks[1], (b, 1, h, dh), jnp.float32),
        pos=(new_len - 1)[:, None], new_len=new_len,
        k_cache=jax.random.normal(ks[2], (b, hkv, s_max, dh), jnp.float32))
    if getattr(policy, "needs_meta", False):
        # QuestPolicy consumes the selection-metadata cache (ISSUE 5);
        # bulk-build it from the same K view the model's prefill would
        from repro.core import metacache as mc
        cache = mc.prefill_metacache(
            mc.init_metacache(b, s_max // bs, hkv, dh), inp.k_cache,
            new_len, bs)
        inp = inp._replace(meta_kmin=cache.kmin, meta_kmax=cache.kmax)
    idx = np.asarray(policy.select(inp, cfg))
    k_budget = max(1, cfg.gate.token_budget // bs)
    assert idx.shape == (b, hkv, min(k_budget, s_max // bs))
    assert idx.dtype == np.int32
    n_valid = np.asarray(-(-new_len // bs))
    for bi in range(b):
        for hi in range(hkv):
            sel = idx[bi, hi][idx[bi, hi] >= 0]
            assert len(set(sel.tolist())) == len(sel), "duplicate blocks"
            assert (sel < n_valid[bi]).all(), \
                f"selected invisible block: {sel} vs {n_valid[bi]}"
            # trailing (possibly partial) block is force-selected
            assert (n_valid[bi] - 1) in sel


def test_sliding_window_selects_sink_and_tail():
    cfg = G.tiny_cfg()
    new_len = jnp.array([41], jnp.int32)           # 6 visible blocks
    inp = pol.SelectionInputs(
        q_nope=jnp.zeros((1, 1, cfg.n_heads, cfg.resolved_head_dim)),
        qr=jnp.zeros((1, 1, cfg.n_heads, cfg.resolved_head_dim)),
        pos=(new_len - 1)[:, None], new_len=new_len,
        k_cache=jnp.zeros((1, cfg.n_kv_heads, 64, cfg.resolved_head_dim)))
    idx = np.asarray(SlidingWindowPolicy().select(inp, cfg))[0, 0]
    # budget 32 tok / bs 8 = 4 slots: TRAILING block first (so runtime
    # budget masks can never drop it), then sink 0, then the window
    assert idx.tolist() == [5, 0, 4, 3]
    # tiny context: window+sink covers everything, rest padded with -1
    idx2 = np.asarray(SlidingWindowPolicy().select(
        inp._replace(new_len=jnp.array([9], jnp.int32)), cfg))[0, 0]
    assert idx2.tolist() == [1, 0, -1, -1]
    # one-block context: the sink IS the trailing block — deduped
    idx3 = np.asarray(SlidingWindowPolicy().select(
        inp._replace(new_len=jnp.array([3], jnp.int32)), cfg))[0, 0]
    assert idx3.tolist() == [0, -1, -1, -1]


def test_oracle_full_budget_equals_dense():
    """OraclePolicy with budget >= context selects every visible block, so
    its decode logits equal dense decode logits."""
    cfg = G.tiny_cfg().replace(gate=dataclasses.replace(
        G.tiny_cfg().gate, token_budget=4096))
    api, params, toks = _params_and_prompt(cfg)
    _, st0 = api.prefill(params, {"tokens": toks}, cfg, G.MAX_LEN)
    nxt = jnp.array([3, 4])
    lg_d, _, _ = api.decode_step(params, st0, nxt, cfg,
                                 options=DecodeOptions(policy=DensePolicy()))
    lg_o, _, _ = api.decode_step(params, st0, nxt, cfg,
                                 options=DecodeOptions(policy=OraclePolicy()))
    np.testing.assert_allclose(np.asarray(lg_o, np.float32),
                               np.asarray(lg_d, np.float32),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("policy", [QuestPolicy(), OraclePolicy(),
                                    SlidingWindowPolicy()],
                         ids=["quest", "oracle", "sliding_window"])
def test_policy_end_to_end_decode(policy):
    """Every policy decodes end-to-end (contiguous engine): finite logits
    and measured sparsity in [0, 1)."""
    eng, lgs = _decode_with(G.tiny_cfg(), policy)
    assert np.isfinite(lgs).all()
    stats = eng.sparsity_stats()
    assert stats["measured"]
    assert 0.0 <= stats["sparsity"] < 1.0


def test_policy_paged_serve_quest():
    """A non-gate policy through the PAGED serving stack matches its own
    contiguous decode (same parity harness as the gate)."""
    cfg = G.tiny_cfg()
    api, params, _ = _params_and_prompt(cfg)
    opts = DecodeOptions(policy=QuestPolicy())
    eng = DecodeEngine(cfg, params, max_len=128, options=opts)
    rng = np.random.default_rng(7)
    reqs = [{"rid": i, "max_new_tokens": 6,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, pl in enumerate((19, 26))]
    res = eng.serve(reqs, n_slots=2, collect_logits=True)
    for r in reqs:
        logits, st = api.prefill(
            params, {"tokens": jnp.asarray(r["tokens"])[None]}, cfg, 128,
            options=opts)    # builds the quest selection-metadata cache
        lgs = [np.asarray(logits[0], np.float32)]
        t = jnp.argmax(logits, -1).astype(jnp.int32)
        toks = [int(t[0])]
        for _ in range(5):
            t, lg, st, _ = eng._step(params, st, t)
            lgs.append(np.asarray(lg[0], np.float32))
            toks.append(int(t[0]))
        assert res[r["rid"]] == toks
        d = float(np.max(np.abs(res["logits"][r["rid"]] - np.stack(lgs))))
        assert d <= 1e-3, f"rid {r['rid']}: logit diff {d}"


# ---------------------------------------------------------------------------
# 3. sampling
# ---------------------------------------------------------------------------

def test_sampling_greedy_is_argmax_bitwise():
    lg = jax.random.normal(jax.random.PRNGKey(0), (4, 97))
    got = smp.sample(lg, SamplingParams())
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.argmax(lg, -1)))


def test_top_p_sampling_deterministic_under_fixed_key():
    lg = jax.random.normal(jax.random.PRNGKey(1), (3, 211))
    params = SamplingParams(temperature=1.5, top_p=0.9, top_k=50)
    k1, k2 = jax.random.PRNGKey(42), jax.random.PRNGKey(43)
    a = np.asarray(smp.sample(lg, params, k1))
    b = np.asarray(smp.sample(lg, params, k1))
    np.testing.assert_array_equal(a, b)            # same key -> same tokens
    draws = {tuple(np.asarray(smp.sample(lg, params, jax.random.PRNGKey(s))))
             for s in range(20)}
    assert len(draws) > 1                          # different keys vary


def test_top_p_restricts_to_nucleus():
    """With a peaked distribution and top_p=0.5, only the nucleus tokens
    can ever be drawn."""
    lg = jnp.asarray([[4.0, 3.9, -5.0, -5.0, -6.0]])
    params = SamplingParams(temperature=1.0, top_p=0.5)
    seen = {int(smp.sample(lg, params, jax.random.PRNGKey(s))[0])
            for s in range(64)}
    # nucleus = {0} (p0 ~ 0.52 > 0.5); token 1 admitted only via the
    # keep-while-mass-before < p rule -> {0, 1} at most
    assert seen <= {0, 1}
    lg2 = jnp.asarray([[10.0, 0.0, 0.0, 0.0, 0.0]])
    seen2 = {int(smp.sample(lg2, params, jax.random.PRNGKey(s))[0])
             for s in range(64)}
    assert seen2 == {0}


def test_top_p_tie_at_cutoff_does_not_leak():
    """Tokens tied with the last kept logit must NOT widen the nucleus:
    the filter keeps an exact count, ties broken by lower token id."""
    lg = jnp.asarray([[2.0, 1.0, 1.0, 1.0]])
    # nucleus at p=0.5: token 0 (~0.47) + token 1 crosses 0.5 -> 2 kept
    seen = {int(smp.sample(lg, SamplingParams(temperature=1.0, top_p=0.5),
                           jax.random.PRNGKey(s))[0]) for s in range(128)}
    assert seen == {0, 1}, seen
    # top-k with ties: exactly k survive, lower ids win
    seen_k = {int(smp.sample(lg, SamplingParams(temperature=5.0, top_k=2),
                             jax.random.PRNGKey(s))[0]) for s in range(128)}
    assert seen_k == {0, 1}, seen_k


def test_sparsity_stats_ignores_idle_serve_slots():
    """serve() with a retired/idle slot must not average that slot's
    garbage (rho=0) rows into the measured sparsity: the 2-slot run with
    one immediately-retired request reports the same final sparsity as
    the same request served alone."""
    cfg = G.tiny_cfg()
    _, params, _ = _params_and_prompt(cfg)
    rng = np.random.default_rng(12)
    long_req = {"rid": 0, "max_new_tokens": 10,
                "tokens": rng.integers(0, cfg.vocab_size,
                                       size=(60,)).astype(np.int32)}
    short = {"rid": 1, "max_new_tokens": 1,     # retires at admission
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(9,)).astype(np.int32)}
    eng = DecodeEngine(cfg, params, max_len=128)
    eng.serve([dict(long_req)], n_slots=1)
    alone = eng.sparsity_stats()
    eng.serve([dict(long_req), short], n_slots=2)   # slot 1 idle all run
    mixed = eng.sparsity_stats()
    assert alone["sparsity"] > 0
    assert mixed["sparsity"] == pytest.approx(alone["sparsity"], abs=1e-6)
    assert mixed["sel_blocks"] == pytest.approx(alone["sel_blocks"],
                                                abs=1e-6)


def test_sparsity_stats_reset_between_runs():
    """A run with zero decode steps must not report the PREVIOUS run's
    telemetry as measured."""
    cfg = G.tiny_cfg()
    _, params, toks = _params_and_prompt(cfg)
    eng = DecodeEngine(cfg, params, max_len=G.MAX_LEN)
    eng.generate({"tokens": toks}, 4)
    assert eng.sparsity_stats()["measured"]
    eng.generate({"tokens": toks}, 1)      # prefill only, no decode step
    assert not eng.sparsity_stats()["measured"]


def test_top_k_restricts_support():
    lg = jnp.asarray([[5.0, 4.0, 3.0, 2.0, 1.0]])
    params = SamplingParams(temperature=2.0, top_k=2)
    seen = {int(smp.sample(lg, params, jax.random.PRNGKey(s))[0])
            for s in range(64)}
    assert seen <= {0, 1}


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(temperature=-1.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        smp.sample(jnp.zeros((1, 4)), SamplingParams(temperature=1.0))


def test_generate_with_sampling_reproducible():
    cfg = G.tiny_cfg()
    _, params, toks = _params_and_prompt(cfg)
    opts = DecodeOptions(sampling=SamplingParams(temperature=0.8, top_p=0.95))
    eng = DecodeEngine(cfg, params, max_len=G.MAX_LEN, options=opts)
    key = jax.random.PRNGKey(7)
    a = np.asarray(eng.generate({"tokens": toks}, 6, key=key)["tokens"])
    b = np.asarray(eng.generate({"tokens": toks}, 6, key=key)["tokens"])
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# 4. serve(): per-request overrides
# ---------------------------------------------------------------------------

def test_serve_per_request_budget_override_honored():
    """Same prompt twice: the request with a 1-block budget override must
    measure strictly sparser selection than the unconstrained one, and its
    mean selected blocks must respect the cap (+ forced-block floor)."""
    cfg = G.tiny_cfg()
    _, params, _ = _params_and_prompt(cfg)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, size=(41,)).astype(np.int32)
    reqs = [{"rid": "full", "tokens": prompt, "max_new_tokens": 8},
            {"rid": "tight", "tokens": prompt, "max_new_tokens": 8,
             "budget": cfg.gate.block_size}]       # 1 block -> floor of 2
    eng = DecodeEngine(cfg, params, max_len=128)
    res = eng.serve(reqs, n_slots=2)
    sel = res["stats"]["sel_blocks_by_rid"]
    rho = res["stats"]["sparsity_by_rid"]
    floor = int(cfg.gate.always_first_block) + int(cfg.gate.always_last_block)
    assert sel["tight"] <= floor + 1e-6
    assert sel["full"] > sel["tight"]
    assert rho["tight"] > rho["full"]


def test_serve_budget_override_noop_at_config_budget():
    """budget == the config budget -> bitwise the same tokens/logits as no
    override (the mask never binds)."""
    cfg = G.tiny_cfg()
    _, params, _ = _params_and_prompt(cfg)
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, size=(33,)).astype(np.int32)
    eng = DecodeEngine(cfg, params, max_len=128)
    base = eng.serve([{"rid": 0, "tokens": prompt, "max_new_tokens": 7}],
                     n_slots=1, collect_logits=True)
    over = eng.serve([{"rid": 0, "tokens": prompt, "max_new_tokens": 7,
                       "budget": cfg.gate.token_budget}],
                     n_slots=1, collect_logits=True)
    assert base[0] == over[0]
    np.testing.assert_array_equal(base["logits"][0], over["logits"][0])


def test_serve_no_budget_no_mask_threshold_nongate():
    """Regression: with NO per-request budget there must be NO mask at
    all. threshold-method configs have a selection width without the
    forced floor while budget_select (quest/oracle) floors it — a default
    mask sized off the former used to clip the forced trailing block."""
    cfg = G.tiny_cfg("threshold").replace(gate=dataclasses.replace(
        G.tiny_cfg("threshold").gate, token_budget=8))   # 1 block budget
    api, params, _ = _params_and_prompt(cfg)
    opts = DecodeOptions(policy=QuestPolicy())
    eng = DecodeEngine(cfg, params, max_len=128, options=opts)
    rng = np.random.default_rng(13)
    req = {"rid": 0, "max_new_tokens": 6,
           "tokens": rng.integers(0, cfg.vocab_size,
                                  size=(27,)).astype(np.int32)}
    res = eng.serve([req], n_slots=1, collect_logits=True)
    logits, st = api.prefill(params,
                             {"tokens": jnp.asarray(req["tokens"])[None]},
                             cfg, 128, options=opts)
    lgs = [np.asarray(logits[0], np.float32)]
    t = jnp.argmax(logits, -1).astype(jnp.int32)
    toks = [int(t[0])]
    for _ in range(5):
        t, lg, st, _ = eng._step(params, st, t)
        lgs.append(np.asarray(lg[0], np.float32))
        toks.append(int(t[0]))
    assert res[0] == toks
    assert float(np.max(np.abs(res["logits"][0] - np.stack(lgs)))) <= 1e-3


def test_measure_sparsity_off_compiles_out_telemetry():
    """measure_sparsity=False: identical tokens, measured=False stats."""
    cfg = G.tiny_cfg()
    _, params, toks = _params_and_prompt(cfg)
    eng_on = DecodeEngine(cfg, params, max_len=G.MAX_LEN)
    eng_off = DecodeEngine(cfg, params, max_len=G.MAX_LEN,
                           options=DecodeOptions(measure_sparsity=False))
    a = np.asarray(eng_on.generate({"tokens": toks}, 5)["tokens"])
    b = np.asarray(eng_off.generate({"tokens": toks}, 5)["tokens"])
    np.testing.assert_array_equal(a, b)
    assert eng_on.sparsity_stats()["measured"]
    assert not eng_off.sparsity_stats()["measured"]


def test_serve_budget_mask_keeps_trailing_block_sliding_window():
    """A 1-block per-request budget on SlidingWindowPolicy must still
    attend the trailing block (slot order contract: trailing first)."""
    cfg = G.tiny_cfg().replace(gate=dataclasses.replace(
        G.tiny_cfg().gate, always_first_block=False))   # floor = 1
    _, params, _ = _params_and_prompt(cfg)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab_size, size=(41,)).astype(np.int32)
    eng = DecodeEngine(cfg, params, max_len=128,
                       options=DecodeOptions(policy=SlidingWindowPolicy()))
    res = eng.serve([{"rid": 0, "tokens": prompt, "max_new_tokens": 6,
                      "budget": cfg.gate.block_size}], n_slots=1)
    # cap = 1 block -> exactly the trailing block survives each step
    assert abs(res["stats"]["sel_blocks_by_rid"][0] - 1.0) < 1e-6
    assert np.isfinite(res["stats"]["sparsity_by_rid"][0])


def test_sparsity_stats_full_keyset_before_any_decode():
    """sparsity_stats() before a decode step (e.g. max_new_tokens=1: the
    prefill alone satisfies the request) must return the full key set so
    shipped callers can format it unconditionally."""
    cfg = G.tiny_cfg()
    _, params, toks = _params_and_prompt(cfg)
    eng = DecodeEngine(cfg, params, max_len=G.MAX_LEN)
    fresh = eng.sparsity_stats()
    eng.generate({"tokens": toks}, 4)
    measured = eng.sparsity_stats()
    assert not fresh["measured"] and measured["measured"]
    assert set(fresh) == set(measured)


def test_serve_per_request_sampling():
    """Mixed greedy + stochastic requests: the greedy request reproduces
    the all-greedy trajectory; the stochastic one is seed-deterministic."""
    cfg = G.tiny_cfg()
    _, params, _ = _params_and_prompt(cfg)
    rng = np.random.default_rng(8)
    p1 = rng.integers(0, cfg.vocab_size, size=(21,)).astype(np.int32)
    p2 = rng.integers(0, cfg.vocab_size, size=(17,)).astype(np.int32)
    hot = SamplingParams(temperature=1.5, top_k=8)
    reqs = [{"rid": "g", "tokens": p1, "max_new_tokens": 6},
            {"rid": "s", "tokens": p2, "max_new_tokens": 6, "sampling": hot}]
    eng = DecodeEngine(cfg, params, max_len=128)
    r1 = eng.serve(reqs, n_slots=2, sample_seed=11)
    r2 = eng.serve(reqs, n_slots=2, sample_seed=11)
    assert r1["g"] == r2["g"] and r1["s"] == r2["s"]   # seed-deterministic
    greedy_only = eng.serve([reqs[0]], n_slots=1)
    assert r1["g"] == greedy_only["g"]                 # greedy row unchanged


# ---------------------------------------------------------------------------
# 5. DecodeOptions statics
# ---------------------------------------------------------------------------

def test_decode_options_hashable_and_validated():
    a = DecodeOptions()
    b = DecodeOptions(policy=GatePolicy())
    assert a == b and hash(a) == hash(b)      # one jit cache entry
    assert hash(DecodeOptions(policy=QuestPolicy())) != hash(a) or True
    assert DecodeOptions(policy=QuestPolicy()) != a
    with pytest.raises(ValueError):
        DecodeOptions(kernel_impl="cuda")
    with pytest.raises(ValueError):
        DecodeOptions(budget_override=0)
    with pytest.raises(ValueError):
        DecodeOptions(policy=QuestPolicy(), kernel_impl="sharded")
    cfg = G.tiny_cfg()
    assert DecodeOptions().max_selected(cfg) is None
    assert DecodeOptions(budget_override=16).max_selected(cfg) == 2
    assert default_options(cfg) == DecodeOptions()


def test_kernel_impl_follows_platform():
    """The default decode path is the platform's own (jnp on this CPU
    backend); the compiled TPU kernels are refused off a TPU instead of
    quietly falling back to interpret mode or the jnp path."""
    assert jax.default_backend() != "tpu"
    assert DecodeOptions().kernel_impl is None
    assert DecodeOptions().impl == pol.platform_kernel_impl() == "ref"
    assert DecodeOptions(kernel_impl="pallas_interpret").impl == \
        "pallas_interpret"
    cfg = G.tiny_cfg()
    _, params, _ = _params_and_prompt(cfg)
    with pytest.raises(ValueError, match="compiled TPU kernels"):
        DecodeEngine(cfg, params, max_len=G.MAX_LEN,
                     options=DecodeOptions(kernel_impl="pallas"))
    DecodeEngine(cfg, params, max_len=G.MAX_LEN,
                 options=DecodeOptions(kernel_impl="pallas_interpret"))


def test_engine_budget_override_static():
    """budget_override in the OPTIONS (static, recompiles) narrows the
    compiled selection width end to end."""
    cfg = G.tiny_cfg()
    _, params, toks = _params_and_prompt(cfg)
    eng = DecodeEngine(cfg, params, max_len=G.MAX_LEN,
                       options=DecodeOptions(budget_override=2
                                             * cfg.gate.block_size))
    eng.generate({"tokens": toks}, 4)
    stats = eng.sparsity_stats()
    assert stats["measured"] and stats["sel_blocks"] <= 2.0 + 1e-6

def test_no_sparse_impl_kwarg_left_in_src():
    """Acceptance grep: the sparse/sparse_impl kwarg threading is gone —
    no hits outside core/policy.py (the DecodeOptions internals)."""
    src = os.path.join(HERE, "..", "src")
    r = subprocess.run(["grep", "-rln", "sparse_impl", src],
                       capture_output=True, text=True)
    hits = [os.path.relpath(p, src) for p in r.stdout.split()]
    assert all(h.endswith("core/policy.py") for h in hits), hits
