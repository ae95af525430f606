"""Quantized (int8) KV/Kg page-pool tests (ISSUE 9).

Numerics contract under test:
  * fused in-kernel dequant == dequant-first reference EXACTLY on the
    jnp ref path (same gathers, same f32 multiply), and to kernel
    tolerance on pallas_interpret;
  * ``quantize='int8'`` serving stays within decode-realistic tolerance
    of the fp engine (symmetric per-(page, head) abs-max/127 scales:
    ~0.4% relative per element, empirically <= ~1.5% of the logit scale
    on the reduced config);
  * preempt -> swap -> resume and evict -> restore round-trip the RAW
    int8 bytes + scale rows, so a tight-pool int8 run is BITWISE equal
    to an ample-pool int8 run;
  * ``quantize=None`` (the default) leaves the decode program
    byte-for-byte unchanged — guarded against tests/golden_policy.npz.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.config import reduced
from repro.core import metacache as mc
from repro.core.policy import DecodeOptions, QuestPolicy
from repro.kernels import ops
from repro.serve import paging as pg
from repro.serve.engine import DecodeEngine
from repro.serve.eviction import EvictionConfig, EvictionManager
from repro.models.registry import get_api

jax.config.update("jax_platform_name", "cpu")

HERE = os.path.dirname(__file__)


# ---------------------------------------------------------------------------
# quantize/dequantize helpers
# ---------------------------------------------------------------------------

def test_quantize_block_scale_semantics():
    """abs-max/127 over VALID rows only; empty/all-zero regions get scale
    1.0 so their dequant is exactly 0; the abs-max element round-trips to
    within half a quantization step."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 2, 8, 4)).astype(np.float32))
    valid = jnp.ones((3, 2, 8, 4), bool)
    q, sc = pg.quantize_block(x, valid)
    assert q.dtype == jnp.int8 and sc.shape == (3, 2, 1)
    amax = np.max(np.abs(np.asarray(x)), axis=(-2, -1))
    np.testing.assert_allclose(np.asarray(sc)[..., 0], amax / 127.0,
                               rtol=1e-6)
    err = np.abs(np.asarray(pg.dequantize_block(q, sc)) - np.asarray(x))
    assert float(err.max()) <= float(amax.max()) / 127.0 * 0.5 + 1e-7
    # garbage rows outside `valid` must not inflate the scale
    x2 = x.at[:, :, 4:].set(1e6)
    valid2 = valid.at[:, :, 4:].set(False)
    _, sc2 = pg.quantize_block(x2, valid2)
    amax2 = np.max(np.abs(np.asarray(x[:, :, :4])), axis=(-2, -1))
    np.testing.assert_allclose(np.asarray(sc2)[..., 0], amax2 / 127.0,
                               rtol=1e-6)
    # empty region -> scale 1.0, dequant exact zero
    qz, scz = pg.quantize_block(jnp.zeros((2, 1, 4, 4)),
                                jnp.zeros((2, 1, 4, 4), bool))
    np.testing.assert_array_equal(np.asarray(scz), 1.0)
    np.testing.assert_array_equal(np.asarray(pg.dequantize_block(qz, scz)),
                                  0.0)


# ---------------------------------------------------------------------------
# kernel-level: fused dequant == dequant-first reference
# ---------------------------------------------------------------------------

def _quant_pool_fixture(seed=0, b=2, hkv=2, g=4, dh=32, nb=6, bs=8, nsel=4):
    """One-layer stacked fp pools [1, P, Hkv, bs, Dh] (read at layer 0) +
    their per-page int8 twins + a forced-last selection."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hkv, g, dh), jnp.float32)
    npool = nb + 1
    kp = jax.random.normal(ks[1], (1, npool, hkv, bs, dh), jnp.float32)
    vp = jax.random.normal(ks[2], (1, npool, hkv, bs, dh), jnp.float32)
    kv_len = jnp.array([nb * bs, nb * bs - 5][:b])
    rng = np.random.default_rng(seed + 3)
    idx = np.full((b, hkv, nsel), -1, np.int32)
    for bi in range(b):
        for hi in range(hkv):
            n = rng.integers(1, nsel + 1)
            idx[bi, hi, :n] = rng.choice(nb, n, replace=False)
        idx[bi, :, 0] = (int(kv_len[bi]) - 1) // bs
    table = jnp.asarray(
        np.stack([1 + np.roll(np.arange(nb), r) for r in range(b)]),
        jnp.int32)
    valid = jnp.ones_like(kp, bool)
    kq, ksc = pg.quantize_block(kp, valid)
    vq, vsc = pg.quantize_block(vp, valid)
    kdq, vdq = pg.dequantize_block(kq, ksc), pg.dequantize_block(vq, vsc)
    return q, kq, vq, ksc, vsc, kdq, vdq, jnp.asarray(idx), table, kv_len


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_paged_fused_dequant_matches_dequant_first(impl):
    (q, kq, vq, ksc, vsc, kdq, vdq, idx, table,
     kv_len) = _quant_pool_fixture()
    bs = kq.shape[3]
    o_fused = ops.paged_sparse_decode(q, kq, vq, 0, idx, table, kv_len,
                                      block_size=bs, impl=impl,
                                      k_scales=ksc, v_scales=vsc)
    o_first = ops.paged_sparse_decode(q, kdq, vdq, 0, idx, table, kv_len,
                                      block_size=bs, impl="ref")
    if impl == "ref":
        np.testing.assert_array_equal(np.asarray(o_fused),
                                      np.asarray(o_first))
    else:
        np.testing.assert_allclose(np.asarray(o_fused),
                                   np.asarray(o_first), atol=1e-5,
                                   rtol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_contiguous_fused_dequant_matches_dequant_first(impl):
    """Contiguous twin: per-block scales [B, Hkv, nb] on the head-major
    cache view."""
    b, hkv, g, dh, nb, bs, nsel = 2, 2, 4, 32, 6, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, dh), jnp.float32)
    kc_ = jax.random.normal(ks[1], (b, hkv, nb * bs, dh), jnp.float32)
    vc_ = jax.random.normal(ks[2], (b, hkv, nb * bs, dh), jnp.float32)
    kv_len = jnp.array([nb * bs, nb * bs - 5])
    rng = np.random.default_rng(9)
    idx = np.full((b, hkv, nsel), -1, np.int32)
    for bi in range(b):
        for hi in range(hkv):
            n = rng.integers(1, nsel + 1)
            idx[bi, hi, :n] = rng.choice(nb, n, replace=False)
        idx[bi, :, 0] = (int(kv_len[bi]) - 1) // bs
    idx = jnp.asarray(idx)
    blk = kc_.reshape(b, hkv, nb, bs, dh)
    kq, ksc = pg.quantize_block(blk, jnp.ones_like(blk, bool))
    blv = vc_.reshape(b, hkv, nb, bs, dh)
    vq, vsc = pg.quantize_block(blv, jnp.ones_like(blv, bool))
    kdq = pg.dequantize_block(kq, ksc).reshape(kc_.shape)
    vdq = pg.dequantize_block(vq, vsc).reshape(vc_.shape)
    o_fused = ops.sparse_decode(
        q, kq.reshape(kc_.shape).astype(jnp.int8),
        vq.reshape(vc_.shape).astype(jnp.int8), idx, kv_len,
        block_size=bs, impl=impl, k_scales=ksc[..., 0], v_scales=vsc[..., 0])
    o_first = ops.sparse_decode(q, kdq, vdq, idx, kv_len, block_size=bs,
                                impl="ref")
    if impl == "ref":
        np.testing.assert_array_equal(np.asarray(o_fused),
                                      np.asarray(o_first))
    else:
        np.testing.assert_allclose(np.asarray(o_fused),
                                   np.asarray(o_first), atol=1e-5,
                                   rtol=1e-5)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_splitk_fused_dequant_matches_plain(impl):
    (q, kq, vq, ksc, vsc, kdq, vdq, idx, table,
     kv_len) = _quant_pool_fixture(seed=5, nsel=5)
    bs = kq.shape[3]
    o_plain = ops.paged_sparse_decode(q, kdq, vdq, 0, idx, table, kv_len,
                                      block_size=bs, impl="ref")
    for ns in (1, 2, 3):
        o_s = ops.paged_sparse_decode_splitk(
            q, kq, vq, 0, idx, table, kv_len, block_size=bs, num_splits=ns,
            impl=impl, k_scales=ksc, v_scales=vsc)
        np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_plain),
                                   atol=1e-5, rtol=1e-5)


def test_fp_path_bitwise_unchanged_with_none_scales():
    """k_scales=None must be the ORIGINAL fp program byte-for-byte — the
    guard that int8 support cannot perturb golden-pinned fp decode."""
    (q, kq, vq, ksc, vsc, kdq, vdq, idx, table,
     kv_len) = _quant_pool_fixture(seed=2)
    bs = kq.shape[3]
    for impl in ("ref", "pallas_interpret"):
        a = ops.paged_sparse_decode(q, kdq, vdq, 0, idx, table, kv_len,
                                    block_size=bs, impl=impl)
        b = ops.paged_sparse_decode(q, kdq, vdq, 0, idx, table, kv_len,
                                    block_size=bs, impl=impl,
                                    k_scales=None, v_scales=None)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# engine-level: int8 serving parity + swap/evict round trips
# ---------------------------------------------------------------------------

def _tiny_cfg(**gate_kw):
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    kw = dict(block_size=8, d_gate=16, token_budget=32)
    kw.update(gate_kw)
    return cfg.replace(gate=dataclasses.replace(cfg.gate, **kw))


def _mk_requests(cfg, specs, seed=7):
    rng = np.random.default_rng(seed)
    return [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(specs)]


def _serve(cfg, params, reqs, options=None, **kw):
    eng = DecodeEngine(cfg, params, max_len=64, options=options)
    return eng.serve([dict(r) for r in reqs], collect_logits=True, **kw)


@pytest.mark.parametrize("options", [
    DecodeOptions(quantize="int8"),
    DecodeOptions(quantize="int8", policy=QuestPolicy(), budget_override=64),
], ids=["gate", "quest"])
def test_serve_quant_int8_close_to_fp(options):
    """Decode-realistic parity: int8 pools track the fp engine to within
    the per-page abs-max quantization budget (~1.5% of the logit scale on
    this config; bound set at 0.05 with headroom). Covers the gate policy
    (Kg finalize from dequantized keys) and Quest (min/max metadata from
    dequantized keys + dequantized trailing-block recompute, scored every
    step).

    Each request is compared through the first row whose greedy token
    differs: row i holds the logits that chose token i, from a context
    (tokens 0..i-1) that both engines share up to that row.

    Quest runs at a budget covering every visible block. At the 32-token
    budget its int8 selection can differ from fp on a near-tie: request 2's
    first sparse step (5 visible blocks, 4 kept) had fp Quest bounds 20.2724
    and 20.2667 for blocks 1 and 3 in layer 0, head 0; the int8 metadata
    moved block 3's bound by 0.009 and the step read block 3 instead of 1,
    moving that row's logits by 0.37 (earlier rows <= 0.005). A swapped
    block is not quantization error; ``test_trailing_meta_int8_within_half_step``
    bounds the metadata itself."""
    cfg = _tiny_cfg()
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _mk_requests(cfg, [(21, 8), (13, 10), (30, 6), (17, 7)])
    res_fp = _serve(cfg, params, reqs,
                    options=dataclasses.replace(options, quantize=None),
                    n_slots=2)
    res_q = _serve(cfg, params, reqs, options=options, n_slots=2)
    assert res_q["stats"]["retired"] == len(reqs)
    for r in reqs:
        rid = r["rid"]
        a, b = res_fp["logits"][rid], res_q["logits"][rid]
        n = min(len(a), len(b))
        diverged = np.nonzero(np.asarray(res_fp[rid][:n])
                              != np.asarray(res_q[rid][:n]))[0]
        n = int(diverged[0]) + 1 if diverged.size else n
        assert n >= 3, f"rid {rid}: tokens diverge at row {n - 1}"
        d = float(np.max(np.abs(a[:n] - b[:n])))
        assert d <= 0.05, f"rid {rid}: int8 logit drift {d}"


def test_trailing_meta_int8_within_half_step():
    """Quest's per-step trailing-block metadata on an int8 pool (dequantized
    page -> key min/max) stays within half a quantization step of the fp
    page's metadata, per (page, KV head): the selection inputs carry only
    quantization error."""
    rng = np.random.default_rng(4)
    npool, hkv, ps, dh = 5, 2, 8, 16
    kp = jnp.asarray(rng.normal(size=(1, npool, hkv, ps, dh)), jnp.float32)
    kq, ksc = pg.quantize_block(kp, jnp.ones_like(kp, bool))
    table = jnp.asarray([[1, 3, 0], [2, 4, 0]], jnp.int32)
    cur_len = jnp.asarray([13, 5], jnp.int32)   # partial pages 3 and 2
    fmin, fmax, fidx = mc.trailing_meta_paged(kp, 0, table, cur_len, ps)
    qmin, qmax, qidx = mc.trailing_meta_paged(kq, 0, table, cur_len, ps,
                                              k_scale=ksc)
    np.testing.assert_array_equal(np.asarray(fidx), np.asarray(qidx))
    phys = np.asarray(table)[np.arange(2), np.asarray(fidx)]
    half = np.asarray(ksc)[0, phys] / 2 + 1e-6               # [S, Hkv, 1]
    assert np.all(np.abs(np.asarray(qmin) - np.asarray(fmin)) <= half)
    assert np.all(np.abs(np.asarray(qmax) - np.asarray(fmax)) <= half)


def test_serve_quant_preempt_swap_resume_bitwise():
    """Swap round trip on the STORED representation: a pool too small for
    the batch forces preempt -> swap -> resume; raw int8 bytes + scale
    rows restore bitwise, so the tight run equals the ample int8 run
    exactly — the same contract the fp engine pins, at 1/4 the swap
    traffic (asserted via the byte counters)."""
    cfg = _tiny_cfg()
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _mk_requests(cfg, [(20, 12), (18, 10), (22, 9)], seed=1)
    opts = DecodeOptions(quantize="int8")
    ample = _serve(cfg, params, reqs, options=opts, n_slots=3)
    assert ample["stats"]["preemptions"] == 0
    tight = _serve(cfg, params, reqs, options=opts, n_slots=3, num_pages=8)
    assert tight["stats"]["preemptions"] > 0
    assert tight["stats"]["retired"] == len(reqs)
    for r in reqs:
        rid = r["rid"]
        assert tight[rid] == ample[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(tight["logits"][rid],
                                      ample["logits"][rid])
    # proportional swap traffic: the same workload on fp pools must move
    # ~4x the bytes (int8 K/V + f32 scale rows vs f32 K/V; kg/meta rows
    # ride along unquantized in both)
    fp_tight = _serve(cfg, params, reqs, options=DecodeOptions(),
                      n_slots=3, num_pages=8)
    if fp_tight["stats"]["preemptions"] == tight["stats"]["preemptions"]:
        q_bytes = tight["stats"]["swapped_out_bytes"]
        fp_bytes = fp_tight["stats"]["swapped_out_bytes"]
        assert q_bytes < fp_bytes / 2.5, (q_bytes, fp_bytes)


def test_serve_quant_eviction_bitwise():
    """RaaS page eviction on int8 pools: evict -> ghost -> restore keeps
    the run bitwise equal to the ample int8 run (PageEntry carries the
    raw int8 page + its scale row)."""
    cfg = _tiny_cfg(token_budget=16)
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _mk_requests(cfg, [(40, 25), (38, 24), (41, 22)], seed=0)
    opts = DecodeOptions(quantize="int8")
    ample = _serve(cfg, params, reqs, options=opts, n_slots=3)
    pool = 1 + (ample["stats"]["peak_pages_used"] + 1) // 2
    res = _serve(cfg, params, reqs, options=opts, n_slots=3,
                 num_pages=pool, eviction=EvictionConfig())
    st = res["stats"]
    assert st["retired"] == len(reqs) and st["failed"] == 0, st["errors"]
    assert st["evictions"] > 0, st
    for r in reqs:
        rid = r["rid"]
        assert res[rid] == ample[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(res["logits"][rid],
                                      ample["logits"][rid])


# ---------------------------------------------------------------------------
# quantize=None golden guard
# ---------------------------------------------------------------------------

def test_quantize_none_keeps_paged_goldens_bitwise():
    """Explicit ``quantize=None`` must take the original code path
    verbatim: replay the golden paged serve workload and require BITWISE
    equality with tests/golden_policy.npz."""
    import capture_golden_policy as G
    gold = np.load(os.path.join(HERE, "golden_policy.npz"))
    G.check_versions(gold)
    cfg = G.tiny_cfg("budget")
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(G.PARAM_SEED), cfg)
    eng = DecodeEngine(cfg, params, max_len=128,
                       options=DecodeOptions(quantize=None))
    res = eng.serve(G.paged_requests(cfg), n_slots=2, collect_logits=True)
    for rid in range(len(G.PAGED_SPECS)):
        np.testing.assert_array_equal(
            np.asarray(res[rid], np.int32), gold[f"paged_rid{rid}_tokens"])
        np.testing.assert_array_equal(
            res["logits"][rid], gold[f"paged_rid{rid}_logits"])


# ---------------------------------------------------------------------------
# eviction restore-cost model (satellite: actual page bytes)
# ---------------------------------------------------------------------------

def test_restore_cost_uses_actual_page_bytes():
    """The victim model's restore cost must come from the victim page's
    ACTUAL byte size: int8 pools restore ~4x cheaper than fp32 pools of
    the same geometry, and per-page kg/kmin/kmax rows are part of the
    PageEntry traffic (they were silently dropped by the old
    (k+v)//num_pages constant)."""
    cfg = _tiny_cfg()
    nl, npages = 2, 9
    fp = pg.init_pages(cfg, npages, nl, with_meta=True, ghost_rows=4)
    q8 = pg.init_pages(cfg, npages, nl, with_meta=True, ghost_rows=4,
                       quantize="int8")
    fp_b = EvictionManager.page_restore_bytes(fp)
    q8_b = EvictionManager.page_restore_bytes(q8)
    ps, dh = cfg.gate.block_size, cfg.resolved_head_dim
    hkv, dg = cfg.n_kv_heads, cfg.gate.d_gate
    # exact accounting: K/V page cut + kg + kmin/kmax rows (+ scale rows)
    kv_fp = 2 * nl * hkv * ps * dh * 4
    meta = nl * hkv * dg * 4 + 2 * nl * hkv * dh * 4
    assert fp_b == kv_fp + meta
    assert q8_b == kv_fp // 4 + meta + 2 * nl * hkv * 4
    assert q8_b < fp_b / 2                       # ~4x cheaper K/V dominates
