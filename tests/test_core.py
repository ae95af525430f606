"""Unit tests for the SeerAttention-R core: gate, distill GT, sparsity
methods, K-compression cache, oracle and Quest baselines."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.config import GateConfig, Rope
from repro.core import attngate as ag
from repro.core import kcache as kc
from repro.core import oracle, quest
from repro.core.distill import (gate_kl_loss, ground_truth_from_blockmax,
                                mask_blockmax_causal)
from repro.core.sparsity import budget_select, threshold_select, sparsity_ratio
from repro.models.common import apply_rope

GCFG = GateConfig(block_size=8, d_gate=16, token_budget=32)


def _gate_params(key, hkv=2, g=2, dh=16):
    return ag.init_attngate(key, n_kv_heads=hkv, group=g, head_dim=dh,
                            cfg=GCFG, dtype="float32")


def test_gate_shapes():
    key = jax.random.PRNGKey(0)
    p = _gate_params(key)
    b, l, hkv, g, dh = 2, 32, 2, 2, 16
    q = jax.random.normal(key, (b, l, hkv * g, dh))
    k = jax.random.normal(key, (b, l, hkv, dh))
    pos = jnp.broadcast_to(jnp.arange(l), (b, l))
    qg = ag.gate_q(p, q, pos, GCFG)
    kg = ag.gate_k(p, k, GCFG)
    assert qg.shape == (b, l, hkv, GCFG.d_gate)
    assert kg.shape == (b, l // GCFG.block_size, hkv, GCFG.d_gate)
    s = ag.gate_scores(qg, kg, q_positions=jnp.arange(l),
                       block_size=GCFG.block_size)
    assert s.shape == (b, hkv, l, l // GCFG.block_size)
    # rows sum to 1 over visible blocks
    np.testing.assert_allclose(np.asarray(s.sum(-1)), 1.0, atol=1e-5)


def test_gate_k_pooling_composition():
    """K branch concatenates max/min/avg pools (paper eq 1b)."""
    key = jax.random.PRNGKey(1)
    k = jax.random.normal(key, (1, 16, 1, 4))
    pooled = ag.pool_k_blocks(k, 8)
    assert pooled.shape == (1, 2, 1, 12)
    blk = np.asarray(k[0, :8, 0])
    np.testing.assert_allclose(np.asarray(pooled[0, 0, 0, :4]), blk.max(0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pooled[0, 0, 0, 4:8]), blk.min(0), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pooled[0, 0, 0, 8:]), blk.mean(0),
                               rtol=1e-5, atol=1e-6)


def test_gate_rope_uses_block_start_positions():
    """Kg with RoPE must equal manual RoPE at positions {0, b, 2b, ...}."""
    key = jax.random.PRNGKey(2)
    p = _gate_params(key, hkv=1, g=1)
    k = jax.random.normal(key, (1, 24, 1, 16))
    kg_rope = ag.gate_k(p, k, GCFG)
    cfg_no = GateConfig(block_size=8, d_gate=16, use_rope=False)
    kg_plain = ag.gate_k(p, k, cfg_no)
    manual = apply_rope(kg_plain, jnp.arange(3) * 8, GCFG.rope)
    np.testing.assert_allclose(np.asarray(kg_rope), np.asarray(manual),
                               atol=1e-5)


def test_ground_truth_group_pooling_and_norm():
    bm = jnp.array(np.random.default_rng(0).normal(size=(2, 4, 8, 4)),
                   jnp.float32)
    bm = mask_blockmax_causal(bm, jnp.arange(8) * 4, 4)  # blocksize 4ish
    gt = ground_truth_from_blockmax(bm, group=2)
    assert gt.shape == (2, 2, 8, 4)
    np.testing.assert_allclose(np.asarray(gt.sum(-1)), 1.0, atol=1e-5)


def test_kl_loss_zero_when_matching():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(1, 2, 4, 8)).astype(np.float32))
    gt = jax.nn.softmax(logits, axis=-1)
    assert float(gate_kl_loss(logits, gt)) < 1e-6
    # and positive otherwise
    assert float(gate_kl_loss(logits + jnp.asarray(
        rng.normal(size=logits.shape).astype(np.float32)), gt)) > 1e-3


def test_budget_select_forces_last_block():
    cfg = GateConfig(block_size=8, token_budget=16)   # 2 blocks
    scores = jnp.zeros((1, 1, 8))
    scores = scores.at[0, 0, 2].set(10.0)             # best block is 2
    n_valid = jnp.array([5])                          # last visible block = 4
    idx, mask = budget_select(scores, n_valid, cfg)
    sel = set(np.asarray(idx[0, 0]).tolist())
    assert 4 in sel and 0 in sel                      # forced last + first
    assert not (set(range(5, 8)) & sel)               # nothing invisible


def test_threshold_select_adaptive_counts():
    cfg = GateConfig(block_size=8, threshold=0.2, method="threshold",
                     always_first_block=False, always_last_block=False)
    probs = jnp.array([[[0.5, 0.3, 0.1, 0.05, 0.05, 0.0, 0.0, 0.0],
                        [0.125] * 8]])
    n_valid = jnp.array([8])
    idx, mask = threshold_select(probs, n_valid, cfg, max_selected=8)
    assert int(mask[0, 0].sum()) == 2                 # 0.5, 0.3 pass
    assert int(mask[0, 1].sum()) == 0                 # uniform under thresh


def test_sparsity_ratio():
    mask = jnp.zeros((1, 1, 10), bool).at[0, 0, :2].set(True)
    r = sparsity_ratio(mask, jnp.array([10]))
    assert abs(float(r) - 0.8) < 1e-6


def test_kcache_update_at_block_boundary():
    key = jax.random.PRNGKey(3)
    p = _gate_params(key, hkv=1, g=1)
    bs = GCFG.block_size
    b, smax, hkv, dh = 2, 4 * bs, 1, 16
    k_raw = jax.random.normal(key, (b, smax, hkv, dh))
    k_hm = jnp.swapaxes(k_raw, 1, 2)            # head-major decode cache
    cache = kc.init_kcache(b, 4, hkv, GCFG.d_gate, jnp.float32)
    # mid-block: no update
    c1 = kc.update_kcache(cache, p, k_hm, jnp.array([bs - 1, bs - 1]), GCFG)
    assert np.all(np.asarray(c1.n_complete) == 0)
    # boundary: block 0 finalised
    c2 = kc.update_kcache(cache, p, k_hm, jnp.array([bs, bs]), GCFG)
    assert np.all(np.asarray(c2.n_complete) == 1)
    expect = ag.gate_k(p, k_raw[:, :bs], GCFG)[:, 0]
    np.testing.assert_allclose(np.asarray(c2.kg[:, :, 0]), np.asarray(expect),
                               atol=1e-5)


def test_kcache_derope_matches_pre_rope():
    """Updating from a post-rope cache (cache_is_roped) must equal updating
    from the pre-rope keys directly."""
    key = jax.random.PRNGKey(4)
    p = _gate_params(key, hkv=1, g=1)
    bs = GCFG.block_size
    k_nope = jax.random.normal(key, (1, 2 * bs, 1, 16))
    pos = jnp.arange(2 * bs)[None]
    k_rope = apply_rope(k_nope, pos, Rope(10000.0))
    cache = kc.init_kcache(1, 2, 1, GCFG.d_gate, jnp.float32)
    cur = jnp.array([2 * bs])
    c_a = kc.update_kcache(cache, p, jnp.swapaxes(k_nope, 1, 2), cur, GCFG)
    c_b = kc.update_kcache(cache, p, jnp.swapaxes(k_rope, 1, 2), cur, GCFG,
                           cache_is_roped=True, rope=Rope(10000.0))
    np.testing.assert_allclose(np.asarray(c_a.kg[:, :, 1]),
                               np.asarray(c_b.kg[:, :, 1]), atol=1e-4)


def test_oracle_beats_random_recall():
    """Oracle selection must recover the truly-heavy blocks."""
    key = jax.random.PRNGKey(5)
    b, s, hkv, g, dh, bs = 1, 128, 2, 2, 16, 8
    k = jax.random.normal(key, (b, s, hkv, dh))
    q = jax.random.normal(key, (b, 1, hkv * g, dh))
    # plant: make block 5 keys align with q
    qh = q[0, 0].reshape(hkv, g, dh).mean(1)
    k = k.at[0, 40:48].set(jnp.broadcast_to(qh * 3, (8, hkv, dh)))
    scores = oracle.oracle_scores_decode(q, k, jnp.array([s]), bs)
    top = np.asarray(jnp.argmax(scores, axis=-1))
    assert np.all(top == 5)


def test_quest_upper_bound_property():
    """Quest score must upper-bound the true q.k for every key in a block."""
    key = jax.random.PRNGKey(6)
    b, s, hkv, dh, bs = 1, 64, 2, 8, 8
    k = jax.random.normal(key, (b, s, hkv, dh))
    q = jax.random.normal(key, (b, 1, hkv, dh))     # g=1
    meta = quest.build_quest_meta(k, jnp.array([s]), bs)
    ub = quest.quest_scores(q, meta, share_group=False)   # [B,H,nb]
    true = jnp.einsum("bhd,bshd->bhs", q[:, 0].astype(jnp.float32),
                      k.astype(jnp.float32))
    true_blk = true.reshape(b, hkv, s // bs, bs).max(-1)
    assert bool(jnp.all(ub + 1e-4 >= true_blk))
