"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes/dtypes, plus hypothesis property tests on the contracts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

# hypothesis is a dev-only dependency (requirements-dev.txt): the sweep
# tests below run without it; only the property tests are skipped.
try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAS_HYPOTHESIS = False

    def _needs_hypothesis(*a, **k):          # no-op decorators
        return lambda f: pytest.mark.skip(
            reason="property tests need hypothesis (requirements-dev.txt)")(f)
    given = settings = _needs_hypothesis

    class st:  # noqa: N801 - stand-in for hypothesis.strategies
        @staticmethod
        def integers(*a, **k):
            return None

        @staticmethod
        def floats(*a, **k):
            return None

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")


def _mk_sparse_inputs(key, b, hkv, g, dh, nb, bs, nsel, dtype):
    """Head-major caches [B, Hkv, S, Dh] — the native decode layout."""
    ks = jax.random.split(key, 4)
    s = nb * bs
    q = jax.random.normal(ks[0], (b, hkv, g, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, dh), jnp.float32).astype(dtype)
    rng = np.random.default_rng(0)
    idx = np.full((b, hkv, nsel), -1, np.int32)
    for bi in range(b):
        for hi in range(hkv):
            n = rng.integers(1, nsel + 1)
            idx[bi, hi, :n] = np.sort(rng.choice(nb, n, replace=False))
    kv_len = jnp.asarray(rng.integers(s - bs + 1, s + 1, size=(b,)), jnp.int32)
    # ensure the last (possibly partial) block is selected (engine contract)
    last_blk = (np.asarray(kv_len) - 1) // bs
    idx[:, :, 0] = last_blk[:, None]
    return q, k, v, jnp.asarray(idx), kv_len


SWEEP = [
    # b, hkv, g, dh, nb, bs, nsel, dtype
    (1, 1, 1, 64, 4, 16, 2, jnp.float32),
    (2, 2, 4, 64, 8, 16, 5, jnp.float32),
    (2, 2, 8, 128, 8, 64, 4, jnp.bfloat16),
    (1, 4, 2, 128, 16, 32, 8, jnp.bfloat16),
    (3, 1, 48, 128, 4, 64, 3, jnp.float32),   # granite-style MQA group
]


@pytest.mark.parametrize("b,hkv,g,dh,nb,bs,nsel,dtype", SWEEP)
def test_block_sparse_decode_matches_ref(b, hkv, g, dh, nb, bs, nsel, dtype):
    q, k, v, idx, kv_len = _mk_sparse_inputs(
        jax.random.PRNGKey(42), b, hkv, g, dh, nb, bs, nsel, dtype)
    o_ref = ref.sparse_decode_ref(q, k, v, idx, kv_len, block_size=bs)
    o_pal = ops.sparse_decode(q, k, v, idx, kv_len, block_size=bs,
                              impl="pallas_interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=tol, rtol=tol)


def test_sparse_decode_full_selection_equals_dense():
    """Selecting ALL blocks must reproduce dense attention exactly."""
    b, hkv, g, dh, nb, bs = 2, 2, 2, 32, 8, 16
    key = jax.random.PRNGKey(1)
    q, k, v, _, _ = _mk_sparse_inputs(key, b, hkv, g, dh, nb, bs, nb,
                                      jnp.float32)
    kv_len = jnp.array([nb * bs, nb * bs - 3])
    idx = jnp.broadcast_to(jnp.arange(nb, dtype=jnp.int32), (b, hkv, nb))
    o_sparse = ref.sparse_decode_ref(q, k, v, idx, kv_len, block_size=bs)
    o_dense = ref.dense_decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(o_sparse), np.asarray(o_dense),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("c", [1, 2, 3, 8])
def test_block_sparse_decode_multiblock_fold(c):
    """Folding C selected blocks per grid step (incl. non-divisible nsel
    and C > nsel) must not change the result vs the jnp oracle."""
    from repro.kernels.block_sparse_decode import block_sparse_decode
    b, hkv, g, dh, nb, bs, nsel = 2, 2, 4, 64, 8, 16, 5
    q, k, v, idx, kv_len = _mk_sparse_inputs(
        jax.random.PRNGKey(11), b, hkv, g, dh, nb, bs, nsel, jnp.float32)
    o_ref = ref.sparse_decode_ref(q, k, v, idx, kv_len, block_size=bs)
    o_pal = block_sparse_decode(q, k, v, idx, kv_len, block_size=bs,
                                blocks_per_step=c, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


GT_SWEEP = [
    # b, lq, h, hkv, dh, bs, q_chunk, dtype
    (1, 64, 2, 1, 32, 16, 16, jnp.float32),
    (2, 128, 4, 2, 64, 32, 32, jnp.float32),
    (2, 128, 8, 2, 64, 64, 64, jnp.bfloat16),
    (1, 256, 4, 4, 128, 64, 128, jnp.bfloat16),
]


@pytest.mark.parametrize("b,lq,h,hkv,dh,bs,qc,dtype", GT_SWEEP)
def test_gate_gt_fwd_matches_ref(b, lq, h, hkv, dh, bs, qc, dtype):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, lq, h, dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, lq, hkv, dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, lq, hkv, dh), jnp.float32).astype(dtype)
    o1, bm1 = ops.gate_gt_attention(q, k, v, block_size=bs, impl="ref")
    o2, bm2 = ops.gate_gt_attention(q, k, v, block_size=bs, q_chunk=qc,
                                    impl="pallas_interpret")
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o2, np.float32),
                               np.asarray(o1, np.float32), atol=tol, rtol=tol)
    clip = lambda x: np.maximum(np.asarray(x, np.float32), -1e29)
    np.testing.assert_allclose(clip(bm2), clip(bm1), atol=tol, rtol=tol)


def test_gate_gt_chunked_matches_ref():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, lq, h, hkv, dh, bs = 2, 96, 4, 2, 32, 16
    q = jax.random.normal(ks[0], (b, lq, h, dh))
    k = jax.random.normal(ks[1], (b, lq, hkv, dh))
    v = jax.random.normal(ks[2], (b, lq, hkv, dh))
    o1, bm1 = ops.gate_gt_attention(q, k, v, block_size=bs, impl="ref")
    o2, bm2 = ops.gate_gt_attention(q, k, v, block_size=bs, q_chunk=32,
                                    impl="chunked")
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5,
                               rtol=2e-5)
    clip = lambda x: np.maximum(np.asarray(x), -1e29)
    np.testing.assert_allclose(clip(bm2), clip(bm1), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# property tests (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    b=st.integers(1, 3), hkv=st.integers(1, 3), g=st.integers(1, 4),
    nb=st.integers(2, 8), seed=st.integers(0, 2**16),
)
def test_property_sparse_decode_subset_invariance(b, hkv, g, nb, seed):
    """Output depends only on the SET of selected blocks: permuting the
    index list and adding -1 padding must not change the result."""
    dh, bs = 16, 8
    q, k, v, idx, kv_len = _mk_sparse_inputs(
        jax.random.PRNGKey(seed), b, hkv, g, dh, nb, bs, nb, jnp.float32)
    o1 = ref.sparse_decode_ref(q, k, v, idx, kv_len, block_size=bs)
    rng = np.random.default_rng(seed)
    idx_np = np.asarray(idx)
    perm = np.stack([np.stack([rng.permutation(idx_np[bi, hi])
                               for hi in range(hkv)]) for bi in range(b)])
    extra = np.full((b, hkv, 2), -1, np.int32)
    idx2 = jnp.asarray(np.concatenate([perm, extra], axis=-1))
    o2 = ref.sparse_decode_ref(q, k, v, idx2, kv_len, block_size=bs)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5,
                               rtol=1e-5)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16), scale=st.floats(0.1, 4.0))
def test_property_gt_blockmax_softmax_identity(seed, scale):
    """softmax over blocks of blockmax == column-blockwise max-pool of the
    true attention row distribution, renormalised (the paper identity)."""
    b, lq, h, dh, bs = 1, 32, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, lq, h, dh)) * scale
    k = jax.random.normal(ks[1], (b, lq, h, dh))
    v = jax.random.normal(ks[2], (b, lq, h, dh))
    _, bm = ops.gate_gt_attention(q, k, v, block_size=bs, impl="ref")
    gt_fast = jax.nn.softmax(bm, axis=-1)
    # explicit route: full attention map -> block max-pool -> renormalise
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dh)
    mask = jnp.arange(lq)[:, None] >= jnp.arange(lq)[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    pm = p.reshape(b, h, lq, lq // bs, bs).max(axis=-1)
    gt_slow = pm / jnp.maximum(pm.sum(axis=-1, keepdims=True), 1e-30)
    np.testing.assert_allclose(np.asarray(gt_fast), np.asarray(gt_slow),
                               atol=1e-5, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_property_budget_selection_monotone(seed):
    """A larger token budget must select a superset of blocks."""
    from repro.config import GateConfig
    from repro.core.sparsity import budget_select
    rng = np.random.default_rng(seed)
    b, hkv, nb, bs = 2, 2, 16, 8
    scores = jnp.asarray(rng.normal(size=(b, hkv, nb)).astype(np.float32))
    n_valid = jnp.asarray(rng.integers(1, nb + 1, size=(b,)), jnp.int32)
    small = GateConfig(block_size=bs, token_budget=2 * bs)
    big = GateConfig(block_size=bs, token_budget=6 * bs)
    _, m_small = budget_select(scores, n_valid, small)
    _, m_big = budget_select(scores, n_valid, big)
    assert bool(jnp.all(~m_small | m_big))


# ---------------------------------------------------------------------------
# paged kernels read the layer-stacked pool at a scalar-prefetched layer
# ---------------------------------------------------------------------------

def _stacked_pools(quant, n_layers=3, npool=9, hkv=2, bs=8, dh=32):
    """[L, P, Hkv, bs, Dh] K/V pools (fp, or int8 plus [L, P, Hkv, 1]
    scales), every layer different."""
    from repro.serve import paging as pg
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    k = jax.random.normal(ks[0], (n_layers, npool, hkv, bs, dh), jnp.float32)
    v = jax.random.normal(ks[1], (n_layers, npool, hkv, bs, dh), jnp.float32)
    if not quant:
        return k, v, None, None
    kq, ksc = pg.quantize_block(k, jnp.ones_like(k, bool))
    vq, vsc = pg.quantize_block(v, jnp.ones_like(v, bool))
    return kq, vq, ksc, vsc


def _paged_call(kernel, pools, layer):
    """One interpret-mode call of a paged kernel on ``pools`` at ``layer``
    (2 slots, scrambled page tables, a forced trailing block)."""
    from repro.kernels import block_sparse_decode as bsd
    k, v, ksc, vsc = pools
    hkv, bs, dh = k.shape[2], k.shape[3], k.shape[4]
    b, g, nb = 2, 4, 6
    table = jnp.asarray(np.stack([1 + np.roll(np.arange(nb), r + 2)
                                  for r in range(b)]), jnp.int32)
    kv_len = jnp.asarray([nb * bs, nb * bs - 5], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(13), (b, hkv, g, dh))
    idx = jnp.asarray([[[5, 0, 2, -1], [5, 3, -1, -1]],
                       [[5, 1, 4, 2], [5, -1, -1, -1]]], jnp.int32)
    kw = dict(block_size=bs, interpret=True, k_scales=ksc, v_scales=vsc)
    if kernel == "splitk":
        return bsd.block_sparse_decode_paged_splitk(
            q, k, v, layer, idx, table, kv_len, num_splits=2, **kw)
    return bsd.block_sparse_decode_paged(q, k, v, layer, idx, table, kv_len,
                                         **kw)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kernel,quant", [
    ("plain", False), ("plain", True), ("splitk", False), ("splitk", True)])
def test_paged_kernel_layer_index_bitwise(kernel, quant, layer):
    """A block-sparse paged kernel at ``layer`` of a 3-layer stacked pool
    equals, bitwise, the same call on that layer's pool alone (a one-layer
    stack at layer 0): the layer index only picks which pool rows are
    streamed."""
    pools = _stacked_pools(quant)
    one = tuple(None if x is None else x[layer:layer + 1] for x in pools)
    got = _paged_call(kernel, pools, jnp.int32(layer))
    want = _paged_call(kernel, one, jnp.int32(0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
