"""Paged-KV continuous-batching subsystem tests.

Parity contract: paged decode (pool + page table + logical->physical
translation) must match the contiguous engine to <= 1e-3 logits — in
practice the sparse ref path is bitwise identical, so the bound is slack
for rounding on other paths. Parity cases run the reduced config in
float32: the contract under test is indexing/scheduling equivalence, not
bf16 reduction noise.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.config import GateConfig, Rope, reduced
from repro.core import attngate as ag
from repro.core.policy import DecodeOptions, DensePolicy
from repro.core import kcache as kc
from repro.kernels import ops
from repro.models.common import apply_rope
from repro.models.registry import get_api
from repro.serve import paging as pg
from repro.serve.engine import DecodeEngine
from repro.serve.scheduler import Request, Scheduler, pages_needed

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# allocator / scheduler (host-side)
# ---------------------------------------------------------------------------

def test_page_allocator_free_list_reuse():
    al = pg.PageAllocator(6)              # pages 1..5 usable, 0 reserved
    a = al.alloc(3)
    b = al.alloc(2)
    assert al.alloc(1) is None            # exhausted
    assert pg.NULL_PAGE not in a + b
    assert len(set(a + b)) == 5
    al.free(a)
    c = al.alloc(3)
    assert set(c) == set(a)               # LIFO reuse of freed pages
    with pytest.raises(ValueError):
        al.free([0])                      # null page is untouchable
    with pytest.raises(ValueError):
        al.free(c[:1] * 2)                # double free


def test_scheduler_fifo_head_of_line():
    sched = Scheduler(n_slots=2, num_pages=8, page_size=4,
                      max_pages_per_seq=4)
    big = Request(rid=0, prompt=np.zeros(12, np.int32), max_new_tokens=5)
    small = Request(rid=1, prompt=np.zeros(4, np.int32), max_new_tokens=2)
    tiny = Request(rid=2, prompt=np.zeros(4, np.int32), max_new_tokens=2)
    for r in (big, small, tiny):
        sched.submit(r)
    admitted = sched.admissions()
    # big takes 4 pages, small takes 2 of the remaining 3; tiny has a slot
    # shortage (2 slots), NOT a page shortage
    assert [r.rid for r in admitted] == [0, 1]
    assert sched.active.sum() == 2
    # finish 'small' -> its pages and slot free -> tiny admitted FIFO
    sched.complete_step(np.array([9, 9], np.int32))
    sched.complete_step(np.array([9, 9], np.int32))
    assert 1 in sched.finished
    admitted = sched.admissions()
    assert [r.rid for r in admitted] == [2]


def test_scheduler_rejects_impossible_request():
    sched = Scheduler(n_slots=1, num_pages=4, page_size=4,
                      max_pages_per_seq=16)
    with pytest.raises(ValueError):
        sched.submit(Request(rid=0, prompt=np.zeros(40, np.int32),
                             max_new_tokens=4))


def test_allocator_min_free_watermark_telemetry():
    al = pg.PageAllocator(8)                  # 7 usable
    al.alloc(3)
    b = al.alloc(2)
    assert al.min_free == 2
    al.free(b)
    assert al.num_free == 4 and al.min_free == 2   # low-watermark sticks


def test_scheduler_lazy_admission_and_watermark():
    """Lazy admission reserves only the pages held NOW (prompt pages) and
    honours the free-page watermark as growth headroom."""
    # reserve mode: prompt 10 + 7 new tokens => ceil(16/4) = 4 pages
    r = Request(rid=0, prompt=np.zeros(10, np.int32), max_new_tokens=7)
    res = Scheduler(n_slots=2, num_pages=16, page_size=4,
                    max_pages_per_seq=4, admission="reserve")
    res.submit(r)
    (a,) = res.admissions()
    assert len(a.pages) == 4
    # lazy mode: only ceil(10/4) = 3 prompt pages at admission
    lz = Scheduler(n_slots=2, num_pages=16, page_size=4,
                   max_pages_per_seq=4, admission="lazy")
    lz.submit(Request(rid=0, prompt=np.zeros(10, np.int32),
                      max_new_tokens=7))
    (b,) = lz.admissions()
    assert len(b.pages) == 3
    # watermark: 5 usable pages, watermark 3 -> a 3-page prompt can NEVER
    # be admitted (only pool - watermark = 2 can ever be free for
    # admission); submit fails fast instead of head-of-line-blocking the
    # queue forever (ISSUE 7 satellite)
    wm = Scheduler(n_slots=2, num_pages=6, page_size=4,
                   max_pages_per_seq=4, admission="lazy", watermark=3)
    with pytest.raises(ValueError, match="head-of-line"):
        wm.submit(Request(rid=1, prompt=np.zeros(10, np.int32),
                          max_new_tokens=2))
    # a prompt that FITS under the watermark but finds the pool busy
    # still waits (transient stall, counted in telemetry)
    wm.submit(Request(rid=2, prompt=np.zeros(8, np.int32),
                      max_new_tokens=2))
    (a2,) = wm.admissions()
    assert len(a2.pages) == 2
    wm.submit(Request(rid=3, prompt=np.zeros(8, np.int32),
                      max_new_tokens=2))
    assert wm.admissions() == []              # 3 free - 2 < watermark 3
    assert wm.admission_stalls == 1


def test_watermark_exempts_swap_in_resumes():
    """The watermark is growth headroom for running requests — a swap-in
    resume must be exempt, or a victim holding more than
    (pool - watermark) content pages could never be re-admitted even with
    the pool fully free."""
    sched = Scheduler(n_slots=2, num_pages=8, page_size=4,
                      max_pages_per_seq=8, admission="lazy", watermark=2)
    req = Request(rid=0, prompt=np.zeros(8, np.int32), max_new_tokens=17)
    sched.submit(req)
    (r,) = sched.admissions()
    assert len(r.pages) == 2                  # prompt pages only
    sched.cur_len[r.slot] = 23                # simulate 15 decode steps
    sched.prepare_step()                      # grow to 23//4 + 1 = 6 pages
    assert len(r.pages) == 6
    sched._preempt(r, None)                   # victim holds 6 content pages
    assert sched.allocator.num_free == 7
    # a FRESH request needing 6 pages would be blocked by the watermark
    # (7 - 6 < 2) — the resume must go through regardless
    (r2,) = sched.admissions()
    assert r2 is req and r2.swapped and len(r2.pages) == 6


def test_scheduler_owns_the_recycled_page_set():
    """Every page the scheduler frees (retire, eviction's free_pages)
    joins the recycled set; mark_live drops rewritten pages; a drain
    within the growth pages takes only those and keeps the rest for the
    end-of-step drain, which takes everything, sorted, once."""
    sched = Scheduler(n_slots=2, num_pages=12, page_size=4,
                      max_pages_per_seq=4, admission="lazy")
    r0 = Request(rid=0, prompt=np.zeros(8, np.int32), max_new_tokens=1)
    r1 = Request(rid=1, prompt=np.zeros(12, np.int32), max_new_tokens=1)
    sched.submit(r0)
    sched.submit(r1)
    a0, a1 = sched.admissions()
    p0, p1 = list(a0.pages), list(a1.pages)
    assert sched.drain_released() == []
    for r in (a0, a1):                        # the prefill's token ends it
        r.out_tokens.append(7)
        assert sched.retire_if_done(r)
    assert sched.released == set(p0 + p1)
    sched.mark_live([p1[0]])                  # rewritten: not stale
    assert sched.drain_released(within=[p0[1], p1[0], 11]) == [p0[1]]
    assert sched.drain_released() == sorted([p0[0]] + p1[1:])
    assert sched.drain_released() == []
    # eviction frees a physical page through the scheduler too
    ids = sched.allocator.alloc(1)
    sched.free_pages(ids)
    assert sched.drain_released() == ids


def test_scheduler_growth_preempts_fewest_generated():
    """Pool exhaustion during lazy growth preempts the request with the
    fewest generated tokens; its pages are freed, the swap callback fires
    first, and it re-queues at the FRONT of pending."""
    sched = Scheduler(n_slots=2, num_pages=6, page_size=4,
                      max_pages_per_seq=6, admission="lazy")
    r0 = Request(rid=0, prompt=np.zeros(8, np.int32), max_new_tokens=9)
    r1 = Request(rid=1, prompt=np.zeros(8, np.int32), max_new_tokens=9)
    sched.submit(r0)
    sched.submit(r1)
    assert len(sched.admissions()) == 2       # 2+2 prompt pages of 5
    # r0 has generated more tokens than r1
    r0.out_tokens = [1, 2, 3]
    r1.out_tokens = [1]
    # force both to need a page: both at a boundary
    sched.cur_len[:] = 8
    swapped = []
    fresh = sched.prepare_step(lambda req: swapped.append(
        (req.rid, req.swap_len, list(req.pages))))
    # r0 takes the last free page; r1's growth finds the pool dry and the
    # fewest-generated victim is r1 itself -> swapped out, not stalled
    assert swapped and swapped[0][0] == 1     # fewest-generated victim
    assert swapped[0][1] == 8                 # swap_len captured pre-free
    assert swapped[0][2], "pages listed at swap time"
    assert r1.swapped and r1.n_preemptions == 1 and not r1.pages
    assert sched.pending[0] is r1             # re-queued at the front
    assert len(r0.pages) == 3 and fresh       # grower got its page
    assert sched.n_preemptions == 1


# ---------------------------------------------------------------------------
# kernel-level parity: paged gather == contiguous
# ---------------------------------------------------------------------------

def _paged_from_contiguous(k_cache, v_cache, nb, bs, perm):
    """Scatter a contiguous head-major [B,Hkv,S,Dh] cache into one-layer
    stacked pools [1,P,Hkv,ps,Dh] (read at layer 0) via a permuted page
    table. Returns pooled arrays + table for batch-shared pools (pages of
    all rows share one pool)."""
    b, hkv, s, dh = k_cache.shape
    npool = b * nb + 1                                  # + null page
    k_pages = np.zeros((npool, hkv, bs, dh), k_cache.dtype)
    v_pages = np.zeros((npool, hkv, bs, dh), v_cache.dtype)
    table = np.zeros((b, nb), np.int32)
    for bi in range(b):
        for j in range(nb):
            phys = 1 + perm[bi * nb + j]
            table[bi, j] = phys
            k_pages[phys] = k_cache[bi, :, j * bs:(j + 1) * bs]
            v_pages[phys] = v_cache[bi, :, j * bs:(j + 1) * bs]
    return (jnp.asarray(k_pages)[None], jnp.asarray(v_pages)[None],
            jnp.asarray(table))


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_paged_sparse_decode_matches_contiguous(impl):
    b, hkv, g, dh, nb, bs, nsel = 2, 2, 4, 32, 6, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, hkv, g, dh), jnp.float32)
    kc_ = jax.random.normal(ks[1], (b, hkv, nb * bs, dh), jnp.float32)
    vc_ = jax.random.normal(ks[2], (b, hkv, nb * bs, dh), jnp.float32)
    kv_len = jnp.array([nb * bs, nb * bs - 5])
    rng = np.random.default_rng(3)
    idx = np.full((b, hkv, nsel), -1, np.int32)
    for bi in range(b):
        for hi in range(hkv):
            n = rng.integers(1, nsel + 1)
            idx[bi, hi, :n] = rng.choice(nb, n, replace=False)
        idx[bi, :, 0] = (int(kv_len[bi]) - 1) // bs      # last block forced
    idx = jnp.asarray(idx)
    o_ct = ops.sparse_decode(q, kc_, vc_, idx, kv_len, block_size=bs,
                             impl="ref")
    perm = rng.permutation(b * nb)                       # scrambled pages
    k_pages, v_pages, table = _paged_from_contiguous(
        np.asarray(kc_), np.asarray(vc_), nb, bs, perm)
    o_pg = ops.paged_sparse_decode(q, k_pages, v_pages, 0, idx, table,
                                   kv_len, block_size=bs, impl=impl)
    tol = 1e-6 if impl == "ref" else 1e-5
    np.testing.assert_allclose(np.asarray(o_pg), np.asarray(o_ct),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_paged_splitk_matches_plain(impl):
    """Split-K paged decode (ISSUE 4): partials over split selected lists
    must combine to the plain paged result; num_splits=1 on the ref path
    is BITWISE the plain reference (the sharded engine's split-free
    case)."""
    b, hkv, g, dh, nb, bs, nsel = 2, 2, 4, 32, 6, 8, 5
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, hkv, g, dh), jnp.float32)
    kc_ = jax.random.normal(ks[1], (b, hkv, nb * bs, dh), jnp.float32)
    vc_ = jax.random.normal(ks[2], (b, hkv, nb * bs, dh), jnp.float32)
    kv_len = jnp.array([nb * bs, nb * bs - 7])
    rng = np.random.default_rng(5)
    idx = np.full((b, hkv, nsel), -1, np.int32)
    for bi in range(b):
        for hi in range(hkv):
            n = rng.integers(1, nsel + 1)
            idx[bi, hi, :n] = rng.choice(nb, n, replace=False)
        idx[bi, :, 0] = (int(kv_len[bi]) - 1) // bs
    idx = jnp.asarray(idx)
    perm = rng.permutation(b * nb)
    k_pages, v_pages, table = _paged_from_contiguous(
        np.asarray(kc_), np.asarray(vc_), nb, bs, perm)
    o_plain = ops.paged_sparse_decode(q, k_pages, v_pages, 0, idx, table,
                                      kv_len, block_size=bs, impl="ref")
    if impl == "ref":
        o1 = ops.paged_sparse_decode_splitk(
            q, k_pages, v_pages, 0, idx, table, kv_len, block_size=bs,
            num_splits=1, impl="ref")
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o_plain))
    for ns in (2, 3, nsel):
        o_s = ops.paged_sparse_decode_splitk(
            q, k_pages, v_pages, 0, idx, table, kv_len, block_size=bs,
            num_splits=ns, impl=impl)
        tol = 1e-6 if impl == "ref" else 1e-5
        np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_plain),
                                   atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# engine-level parity: continuous batching == per-request contiguous decode
# ---------------------------------------------------------------------------

def _tiny_cfg(method="budget"):
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    return cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=32, method=method,
        threshold=2e-2))


def _mk_requests(cfg, specs, seed=0):
    rng = np.random.default_rng(seed)
    return [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(specs)]


def _reference_rollout(eng, req):
    """Per-request contiguous greedy decode; returns (tokens, logits)."""
    params, cfg = eng.params, eng.cfg
    logits, st = eng.api.prefill(
        params, {"tokens": jnp.asarray(req["tokens"])[None]}, cfg,
        eng.max_len)
    lgs = [np.asarray(logits[0], np.float32)]
    t = jnp.argmax(logits, -1).astype(jnp.int32)
    toks = [int(t[0])]
    for _ in range(req["max_new_tokens"] - 1):
        t, lg, st, _ = eng._step(params, st, t)
        lgs.append(np.asarray(lg[0], np.float32))
        toks.append(int(t[0]))
    return toks, np.stack(lgs)


def _assert_serve_parity(cfg, specs, *, n_slots, options=None,
                         num_pages=None, seed=0):
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _mk_requests(cfg, specs, seed)
    eng = DecodeEngine(cfg, params, max_len=128, options=options)
    res = eng.serve(reqs, n_slots=n_slots, num_pages=num_pages,
                    collect_logits=True)
    assert res["stats"]["retired"] == len(reqs)
    for r in reqs:
        toks, lgs = _reference_rollout(eng, r)
        assert res[r["rid"]] == toks, f"rid {r['rid']} token mismatch"
        d = float(np.max(np.abs(res["logits"][r["rid"]] - lgs)))
        assert d <= 1e-3, f"rid {r['rid']}: logit diff {d}"
    return res


def test_serve_ragged_midstream_parity():
    """The acceptance case: ragged prompt lengths (block-unaligned), more
    requests than slots -> mid-stream admission + retirement; paged decode
    must match per-request contiguous decode to <= 1e-3 logits."""
    cfg = _tiny_cfg()
    specs = [(21, 8), (37, 5), (16, 11), (29, 7), (21, 4), (44, 6)]
    res = _assert_serve_parity(cfg, specs, n_slots=3)
    assert res["stats"]["admitted"] == 6
    # with 3 slots and 6 requests, some admissions happened mid-stream
    assert res["stats"]["decode_steps"] < sum(mn for _, mn in specs)


def test_serve_dense_paged_parity():
    cfg = _tiny_cfg()
    specs = [(13, 6), (26, 4), (9, 8)]
    _assert_serve_parity(cfg, specs, n_slots=2,
                         options=DecodeOptions(policy=DensePolicy()))


@pytest.mark.slow
def test_serve_parity_threshold_and_kernel():
    """Extended sweep: threshold selection method and the Pallas interpret
    kernel through the full serving stack."""
    cfg = _tiny_cfg(method="threshold")
    _assert_serve_parity(cfg, [(17, 6), (25, 5), (40, 7)], n_slots=2)
    cfg = _tiny_cfg()
    _assert_serve_parity(cfg, [(21, 6), (34, 5)], n_slots=2,
                         options=DecodeOptions(
                             kernel_impl="pallas_interpret"))


def test_serve_page_exhaustion_queueing_and_reuse():
    """A pool sized for ~one sequence forces serialized admission: requests
    queue on page exhaustion, finish, and freed pages are recycled."""
    cfg = _tiny_cfg()
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    specs = [(24, 6), (24, 6), (24, 6)]
    reqs = _mk_requests(cfg, specs, seed=2)
    need = pages_needed(24, 6, cfg.gate.block_size)
    eng = DecodeEngine(cfg, params, max_len=64)
    # room for one reservation + null page only
    res = eng.serve(reqs, n_slots=3, num_pages=need + 1, collect_logits=True)
    assert res["stats"]["retired"] == 3
    assert res["stats"]["admission_stalls"] > 0          # exhaustion hit
    # page-for-page serialized execution still yields correct outputs
    for r in reqs:
        toks, lgs = _reference_rollout(eng, r)
        assert res[r["rid"]] == toks
        assert float(np.max(np.abs(res["logits"][r["rid"]] - lgs))) <= 1e-3


def test_serve_max_new_one_and_single_token_prompt():
    """Edge raggedness: a request satisfied by prefill alone (max_new=1)
    and a one-token prompt, mixed with a normal request."""
    cfg = _tiny_cfg()
    _assert_serve_parity(cfg, [(10, 1), (1, 5), (18, 4)], n_slots=2)


# ---------------------------------------------------------------------------
# lazy allocation + preemption/swap (ISSUE 4 tentpole)
# ---------------------------------------------------------------------------

def _serve_fixture(specs, seed=0):
    cfg = _tiny_cfg()
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _mk_requests(cfg, specs, seed)
    eng = DecodeEngine(cfg, params, max_len=64)
    return cfg, eng, reqs


def test_serve_lazy_matches_reserve_bitwise():
    """With an ample pool the two admission policies admit identically, so
    lazy must reproduce reserve EXACTLY (physical page placement differs;
    the math is placement-invariant)."""
    _, eng, reqs = _serve_fixture([(21, 8), (37, 5), (16, 11), (29, 7)])
    res_l = eng.serve([dict(r) for r in reqs], n_slots=2,
                      collect_logits=True, admission="lazy")
    res_r = eng.serve([dict(r) for r in reqs], n_slots=2,
                      collect_logits=True, admission="reserve")
    assert res_l["stats"]["preemptions"] == 0
    for r in reqs:
        assert res_l[r["rid"]] == res_r[r["rid"]]
        np.testing.assert_array_equal(res_l["logits"][r["rid"]],
                                      res_r["logits"][r["rid"]])


def test_preemption_roundtrip_bitwise_lossless():
    """The acceptance case: a pool too small for the admitted batch's
    full lifetimes forces preempt -> swap out -> re-admit -> restore; every
    request's tokens AND logits must be bitwise identical to an
    unpreempted run, and rid-keyed telemetry must survive the slot
    recycling."""
    _, eng, reqs = _serve_fixture([(20, 12), (18, 10), (22, 9)])
    ample = eng.serve([dict(r) for r in reqs], n_slots=3,
                      collect_logits=True)
    assert ample["stats"]["preemptions"] == 0
    tight = eng.serve([dict(r) for r in reqs], n_slots=3, num_pages=8,
                      collect_logits=True)
    st = tight["stats"]
    assert st["preemptions"] > 0
    assert st["resumed"] == st["preemptions"]
    assert st["retired"] == len(reqs)
    assert st["retired_preempted"] > 0
    assert st["retired_clean"] == st["retired"] - st["retired_preempted"]
    assert st["swapped_out_bytes"] == st["swapped_in_bytes"] > 0
    for r in reqs:
        rid = r["rid"]
        assert tight[rid] == ample[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(tight["logits"][rid],
                                      ample["logits"][rid])
    # per-request sparsity telemetry is rid-keyed: it must cover every
    # request (preempted ones included) with the same values as unpreempted
    for rid, rho in ample["stats"]["sparsity_by_rid"].items():
        assert rid in st["sparsity_by_rid"]
        np.testing.assert_allclose(st["sparsity_by_rid"][rid], rho,
                                   atol=1e-6)


def test_pool_exhaustion_preempts_instead_of_stalling():
    """Under lazy admission a dry pool triggers preemption (forward
    progress for the survivors) rather than an admission failure; the
    same pool under reserve admission serializes execution instead.
    Lazy sustains a strictly larger admitted batch at the same pool."""
    _, eng, reqs = _serve_fixture([(12, 14), (12, 14), (12, 14)])
    need = pages_needed(12, 14, 8)            # 4 pages full lifetime
    pool = need + 3                           # < 2 full reservations
    lazy = eng.serve([dict(r) for r in reqs], n_slots=3, num_pages=pool,
                     collect_logits=True)
    res = eng.serve([dict(r) for r in reqs], n_slots=3, num_pages=pool,
                    admission="reserve", collect_logits=True)
    assert lazy["stats"]["retired"] == res["stats"]["retired"] == 3
    assert lazy["stats"]["preemptions"] > 0
    assert res["stats"]["preemptions"] == 0
    assert lazy["stats"]["max_active_slots"] > res["stats"]["max_active_slots"]
    assert lazy["stats"]["mean_active_slots"] > res["stats"]["mean_active_slots"]
    for r in reqs:                            # both remain exact
        np.testing.assert_array_equal(lazy["logits"][r["rid"]],
                                      res["logits"][r["rid"]])


def test_preemption_with_per_request_budget_and_sampling():
    """Slot-recycled per-request overrides (budget cap, stochastic
    sampling chain) must survive a swap/re-admit cycle: the preempted run
    reproduces the ample-pool run exactly."""
    from repro.serve.sampling import SamplingParams
    cfg, eng, reqs = _serve_fixture([(20, 9), (18, 8), (21, 7)])
    reqs[0]["budget"] = 16                    # 2-block cap
    reqs[1]["sampling"] = SamplingParams(temperature=0.7, top_k=8)
    ample = eng.serve([dict(r) for r in reqs], n_slots=3,
                      collect_logits=True, sample_seed=3)
    tight = eng.serve([dict(r) for r in reqs], n_slots=3, num_pages=8,
                      collect_logits=True, sample_seed=3)
    assert tight["stats"]["preemptions"] > 0
    for r in reqs:
        rid = r["rid"]
        assert tight[rid] == ample[rid]
        np.testing.assert_array_equal(tight["logits"][rid],
                                      ample["logits"][rid])


# ---------------------------------------------------------------------------
# paged K-compression cache: incremental update == prefill recomputation
# ---------------------------------------------------------------------------

def _kg_fixture(seed, n_pages_seq=3):
    ps, hkv, dh, dg = 4, 2, 8, 8
    gcfg = GateConfig(block_size=ps, d_gate=dg)
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    gate = ag.init_attngate(k1, n_kv_heads=hkv, group=2, head_dim=dh,
                            cfg=gcfg, dtype="float32")
    t_total = n_pages_seq * ps
    k_nope = jax.random.normal(k2, (1, t_total, hkv, dh), jnp.float32)
    return gcfg, gate, k_nope, ps, hkv, dh, dg


def _run_paged_appends(gcfg, gate, k_nope, ps, hkv, dh, dg, t_total):
    """Token-by-token append into paged storage (single slot, scrambled
    physical pages, layer 1 of a two-layer stack); returns (layer 1's
    kg_pages, page_table)."""
    n_pages = t_total // ps
    npool = n_pages + 2
    k_pages = jnp.zeros((2, npool, hkv, ps, dh), jnp.float32)
    v_pages = jnp.zeros((2, npool, hkv, ps, dh), jnp.float32)
    kg_pages = jnp.zeros((2, npool, hkv, dg), jnp.float32)
    # physical ids deliberately not in logical order
    table = np.zeros((1, n_pages), np.int32)
    table[0] = 1 + np.roll(np.arange(n_pages), 1)
    table_j = jnp.asarray(table)
    active = jnp.ones((1,), bool)
    rope = Rope(10000.0)
    for t in range(t_total):
        pos = jnp.full((1, 1), t, jnp.int32)
        kr = apply_rope(k_nope[:, t:t + 1], pos, rope)[:, 0]
        k_pages, v_pages, kg_pages = pg.append_token_paged(
            k_pages, v_pages, kg_pages, 1, kr, kr, table_j,
            jnp.full((1,), t, jnp.int32), active, gate, gcfg,
            rope=rope)
    assert not np.asarray(kg_pages[0]).any()     # layer 0 untouched
    return kg_pages[1], table


def test_paged_kg_matches_prefill_recompute():
    gcfg, gate, k_nope, ps, hkv, dh, dg = _kg_fixture(0)
    t_total = k_nope.shape[1]
    kg_pages, table = _run_paged_appends(gcfg, gate, k_nope, ps, hkv, dh,
                                         dg, t_total)
    n_pages = t_total // ps
    cache = kc.init_kcache(1, n_pages, hkv, dg, jnp.float32)
    cache = kc.prefill_kcache(cache, gate, k_nope, gcfg)
    for j in range(n_pages):
        got = np.asarray(kg_pages[table[0, j]])
        want = np.asarray(cache.kg[0, :, j])         # kg head-major
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2**16), n_pages_seq=st.integers(1, 4))
    def test_property_paged_kg_prefill_equivalence(seed, n_pages_seq):
        """At every block boundary, the paged incremental Kg update (write
        post-rope, un-rope, pool, project) must equal bulk prefill_kcache
        recomputation on the pre-rope prefix — the invariant that keeps
        the paged gate cache trustworthy under arbitrary page layouts."""
        gcfg, gate, k_nope, ps, hkv, dh, dg = _kg_fixture(seed, n_pages_seq)
        t_total = n_pages_seq * ps
        kg_pages, table = _run_paged_appends(gcfg, gate, k_nope, ps, hkv,
                                             dh, dg, t_total)
        cache = kc.init_kcache(1, n_pages_seq, hkv, dg, jnp.float32)
        cache = kc.prefill_kcache(cache, gate, k_nope, gcfg)
        for j in range(n_pages_seq):
            np.testing.assert_allclose(
                np.asarray(kg_pages[table[0, j]]),
                np.asarray(cache.kg[0, :, j]), atol=2e-5, rtol=2e-5)
except ImportError:  # pragma: no cover - hypothesis is optional (dev dep)
    pass
