"""Linear RoPE scaling (deepseek_coder_33b's published x4) at every RoPE
and inverse-RoPE site.

The model's RoPE rotates position ``t`` by the angles of ``t / factor``;
the Kg rows of the gate are built from PRE-RoPE keys, so every path that
finalizes a Kg row from the stored post-RoPE keys has to un-rope them with
the same scaled RoPE. Checked here on a CPU-sized config with a 7:1 GQA
group (14 query / 2 KV heads) and factor 4, in float32:

* the engine (bucketed prefill, then paged decode through
  ``DecodeEngine.serve``) against the plain reference
  ``bench/reference/dense_gate_lm_rope_linear.py``, logit for logit;
* the contiguous, paged and paged x sharded Kg rows against ``gate_k`` of
  the pre-RoPE keys;
* dropping the factor from the forward RoPE, or from the un-rope alone,
  fails each comparison;
* a configuration without scaling lowers to the same decode step as
  before scaling existed.
"""
import dataclasses
import functools
import hashlib
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.config import Rope, RopeScaling, reduced
from repro.core import attngate as ag
from repro.core import kcache as kc
from repro.core.policy import default_options
from repro.models import attn_core, common, transformer
from repro.models.registry import get_api
from repro.serve import paging as pg
from repro.serve.engine import DecodeEngine

jax.config.update("jax_platform_name", "cpu")

HERE = Path(__file__).resolve().parent
REF_PATH = HERE.parent / "bench" / "reference" / "dense_gate_lm_rope_linear.py"
SCALED = Rope(100000.0, 4.0)
# served logits of the engine against the float32 reference
LOGIT_TOL = 1e-3
# Kg rows: paged/contiguous finalize against gate_k of the pre-RoPE keys
KG_TOL = 2e-5


def _reference():
    spec = importlib.util.spec_from_file_location("ref_rope_linear",
                                                  REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg():
    """deepseek_coder_33b cut to CPU size, keeping its RoPE (base 1e5,
    linear x4) and a 7:1 group; block 8, d_gate 16, budget 4 blocks."""
    cfg = reduced(configs.get("deepseek_coder_33b"), n_heads=14,
                  n_kv_heads=2).replace(dtype="float32")
    assert cfg.rope == SCALED and cfg.gqa_group == 7
    return cfg


def _conf(cfg):
    """The configuration as the reference reads it (published keys)."""
    g = cfg.gate
    return {"num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "hidden_size": cfg.d_model,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": dataclasses.asdict(cfg.rope_scaling),
            "qk_norm": cfg.qk_norm, "budget_tokens": g.token_budget,
            "gate": {"block_size": g.block_size, "d_gate": g.d_gate,
                     "rope_theta": g.rope_theta, "use_rope": g.use_rope}}


SPECS = [(41, 40), (57, 32), (70, 24)]     # (prompt, new tokens)


def _served_vs_reference():
    """Serve SPECS through the paged engine (2 slots: mid-stream
    admission); per request, the widest |served logit - reference logit|
    and the widest reference gap (best logit - logit of the served
    token)."""
    cfg = _cfg()
    params = get_api(cfg).init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(11)
    reqs = [{"rid": i, "max_new_tokens": n,
             "tokens": rng.integers(0, cfg.vocab_size, size=(p,)
                                    ).astype(np.int32)}
            for i, (p, n) in enumerate(SPECS)]
    eng = DecodeEngine(cfg, params, max_len=128)
    res = eng.serve([dict(r) for r in reqs], n_slots=2, collect_logits=True)
    assert res["stats"]["retired"] == len(reqs)
    ref, conf = _reference(), _conf(cfg)
    n_out = max(n for _, n in SPECS)
    w, spec = ref._head(params)
    out = []
    for r in reqs:
        p, toks = len(r["tokens"]), res[r["rid"]]
        seq = np.zeros((128,), np.int32)
        seq[:p] = r["tokens"]
        seq[p:p + len(toks) - 1] = toks[:-1]
        h = ref.final_hidden(params, conf, seq, p, n_out)[:len(toks)]
        want = np.asarray(ref._mm(spec, h, w, False))
        got = res["logits"][r["rid"]]
        gap = want.max(-1) - want[np.arange(len(toks)), toks]
        out.append((float(np.max(np.abs(got - want))), float(gap.max())))
    return out


def _drop_factor_forward(monkeypatch):
    """The model's forward RoPE (prefill and the decode append) loses the
    factor; the Kg un-rope keeps it."""
    def unscaled(x, positions, rope):
        return common.apply_rope(x, positions, Rope(rope.theta))
    monkeypatch.setattr(attn_core, "apply_rope", unscaled)
    monkeypatch.setattr(transformer, "apply_rope", unscaled)


def _drop_factor_unrope(monkeypatch):
    """The Kg un-rope of a completed page loses the factor; the forward
    RoPE keeps it."""
    orig = kc.finalize_block_kg

    def unscaled(*a, rope, **kw):
        return orig(*a, rope=Rope(rope.theta), **kw)
    monkeypatch.setattr(kc, "finalize_block_kg", unscaled)
    monkeypatch.setattr(pg, "finalize_block_kg", unscaled)


def test_served_logits_match_scaled_reference():
    """Prefill and paged decode with gate selection at a 4-block budget
    give the reference's logits at every served token, and every served
    token is the reference's best."""
    for dlogit, gap in _served_vs_reference():
        assert dlogit <= LOGIT_TOL, dlogit
        assert gap <= LOGIT_TOL, gap


@pytest.mark.parametrize("drop", [_drop_factor_forward, _drop_factor_unrope],
                         ids=["forward_rope", "kg_unrope"])
def test_served_logits_miss_reference_without_factor(monkeypatch, drop):
    """The same comparison fails when the factor is dropped from the
    forward RoPE, or from the Kg un-rope alone (selection then reads Kg
    rows pooled from keys still rotated by 3/4 of their angle)."""
    drop(monkeypatch)
    worst = max(d for d, _ in _served_vs_reference())
    assert worst > 100 * LOGIT_TOL, worst


# ---------------------------------------------------------------------------
# Kg rows under scaling
# ---------------------------------------------------------------------------

def _kg_fixture(n_blocks=5):
    cfg = _cfg()
    gcfg = cfg.gate
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    gate = ag.init_attngate(k1, n_kv_heads=hkv, group=cfg.gqa_group,
                            head_dim=dh, cfg=gcfg, dtype="float32")
    k_nope = jax.random.normal(
        k2, (1, n_blocks * gcfg.block_size, hkv, dh), jnp.float32)
    want = np.asarray(ag.gate_k(gate, k_nope, gcfg))[0]   # [nb, Hkv, Dg]
    return gcfg, gate, k_nope, want


def _kg_contiguous(gcfg, gate, k_nope, fwd, unrope):
    """update_kcache at every block boundary over a post-RoPE cache."""
    t = k_nope.shape[1]
    k_hm = jnp.swapaxes(common.apply_rope(
        k_nope, jnp.arange(t)[None], fwd), 1, 2)          # [1,Hkv,T,Dh]
    nb = t // gcfg.block_size
    cache = kc.init_kcache(1, nb, k_nope.shape[2], gcfg.d_gate, jnp.float32)
    for j in range(nb):
        cache = kc.update_kcache(
            cache, gate, k_hm, jnp.array([(j + 1) * gcfg.block_size]), gcfg,
            cache_is_roped=True, rope=unrope)
    return np.asarray(jnp.swapaxes(cache.kg[0], 0, 1))   # [nb, Hkv, Dg]


def _kg_paged(gcfg, gate, k_nope, fwd, unrope):
    """append_token_paged token by token into scrambled pages of layer 1
    of a two-layer stack."""
    ps, (_, t, hkv, dh) = gcfg.block_size, k_nope.shape
    nb = t // ps
    k_pages = jnp.zeros((2, nb + 2, hkv, ps, dh), jnp.float32)
    kg_pages = jnp.zeros((2, nb + 2, hkv, gcfg.d_gate), jnp.float32)
    table = 1 + np.roll(np.arange(nb), 2)[None].astype(np.int32)
    for i in range(t):
        kr = common.apply_rope(k_nope[:, i:i + 1], jnp.full((1, 1), i),
                               fwd)[:, 0]
        k_pages, _, kg_pages = pg.append_token_paged(
            k_pages, k_pages, kg_pages, 1, kr, kr, jnp.asarray(table),
            jnp.full((1,), i, jnp.int32), jnp.ones((1,), bool), gate, gcfg,
            rope=unrope)
    return np.asarray(kg_pages[1][table[0]])


@pytest.mark.parametrize("path", [_kg_contiguous, _kg_paged],
                         ids=["contiguous", "paged"])
def test_kg_rows_equal_gate_k_of_prerope_keys(path):
    gcfg, gate, k_nope, want = _kg_fixture()
    got = path(gcfg, gate, k_nope, SCALED, SCALED)
    np.testing.assert_allclose(got, want, atol=KG_TOL, rtol=KG_TOL)


@pytest.mark.parametrize("path", [_kg_contiguous, _kg_paged],
                         ids=["contiguous", "paged"])
@pytest.mark.parametrize("fwd,unrope", [(Rope(1e5), SCALED),
                                        (SCALED, Rope(1e5))],
                         ids=["forward_unscaled", "unrope_unscaled"])
def test_kg_rows_miss_without_factor(path, fwd, unrope):
    gcfg, gate, k_nope, want = _kg_fixture()
    got = path(gcfg, gate, k_nope, fwd, unrope)
    assert np.max(np.abs(got - want)) > 1e3 * KG_TOL


def test_kg_rows_paged_sharded():
    """The paged x sharded body (pools over KV heads) finalizes the same
    Kg rows, and serving the scaled config on the mesh gives the unsharded
    engine's tokens and logits bitwise."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(HERE.parent / "src") + os.pathsep \
        + env.get("PYTHONPATH", "")
    name = "paged_sharded_rope_scaled"
    r = subprocess.run([sys.executable, str(HERE / "sharded_helpers.py"),
                        name], capture_output=True, text=True, timeout=600,
                       env=env)
    assert r.returncode == 0, f"{name} failed:\n{r.stdout}\n{r.stderr}"
    assert f"{name} OK" in r.stdout


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def test_deepseek_coder_carries_published_rope():
    cfg = configs.get("deepseek_coder_33b")
    assert (cfg.rope_theta, cfg.rope_scaling) == \
        (100000.0, RopeScaling("linear", 4.0))
    assert cfg.rope == SCALED and cfg.max_position_embeddings == 16384
    assert (cfg.norm_eps, cfg.tie_embeddings) == (1e-6, False)
    # the gate keeps its own RoPE: base 10000, unscaled
    assert cfg.gate.rope == Rope(10000.0)


@pytest.mark.parametrize("kind", ["dynamic", "yarn", "ntk"])
def test_only_linear_scaling_is_accepted(kind):
    with pytest.raises(ValueError, match="linear"):
        RopeScaling(kind, 4.0)


def test_engine_refuses_positions_past_published_context():
    cfg = _cfg()
    params = get_api(cfg).init_params(jax.random.PRNGKey(0), cfg)
    DecodeEngine(cfg, params, max_len=16384)
    with pytest.raises(ValueError, match="max_position_embeddings"):
        DecodeEngine(cfg, params, max_len=16385)


# sha256 of the lowered paged decode step of reduced qwen3_0_6b (float32,
# default options, 2 slots, 32 pages, 8-page table), captured at the
# commit before RoPE scaling existed. A change that alters the unscaled
# step on purpose recaptures it with _decode_step_text().
UNSCALED_STEP_SHA256 = \
    "4197868dff5c5530efad45ac361dece72c3afc927444089d230f546f0b8cc98e"


def _decode_step_text(cfg) -> str:
    api, opts = get_api(cfg), default_options(cfg)
    params = jax.eval_shape(lambda k: api.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    pages = jax.eval_shape(lambda: pg.init_pages(
        cfg, 32, api.paged_attn_layers(cfg),
        with_meta=opts.policy.needs_meta, quantize=opts.quantize))
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    step = jax.jit(functools.partial(api.decode_step_paged, cfg=cfg,
                                     options=opts, shard=None))
    return step.lower(params, pages, None, i32((2,)), i32((2, 8)),
                      i32((2,)), jax.ShapeDtypeStruct((2,), jnp.bool_)
                      ).as_text()


def _qwen_tiny():
    return reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")


def test_unscaled_decode_step_lowers_as_before():
    text = _decode_step_text(_qwen_tiny())
    assert hashlib.sha256(text.encode()).hexdigest() == UNSCALED_STEP_SHA256


def test_factor_one_adds_no_op_and_factor_four_does():
    cfg = _qwen_tiny()
    plain = _decode_step_text(cfg)
    one = cfg.replace(rope_scaling=RopeScaling("linear", 1.0))
    four = cfg.replace(rope_scaling=RopeScaling("linear", 4.0))
    assert _decode_step_text(one) == plain
    assert _decode_step_text(four) != plain
