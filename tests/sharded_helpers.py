"""Subprocess bodies for multi-device shard_map tests.

Run via `python tests/sharded_helpers.py <name>` with
XLA_FLAGS=--xla_force_host_platform_device_count=8 — pytest's main process
stays single-device (jax locks the device count at first init).
"""
import sys

from repro.launch.mesh import make_mesh


def sharded_decode_parity():
    import dataclasses, functools
    import jax, jax.numpy as jnp
    import numpy as np
    import repro.configs as configs
    from repro.config import reduced
    from repro.core.policy import DecodeOptions
    from repro.data.pipeline import DataState, make_batch
    from repro.models import transformer as tf
    from repro.distributed import sharding as shd

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = reduced(configs.get("qwen3_0_6b"))
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=64,
        local_cap_factor=8.0))  # cap not binding -> exact parity
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    B, PRE, MAX = 4, 120, 256
    batch = {"tokens": make_batch(cfg, B, PRE, DataState(0, 0))["tokens"]}
    logits, st = tf.lm_prefill(params, batch, cfg, max_len=MAX)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    shard = shd.make_shard_fn(mesh)
    with mesh:
        step_ref = jax.jit(functools.partial(
            tf.lm_decode_step, cfg=cfg, options=DecodeOptions()))
        step_sh = jax.jit(functools.partial(
            tf.lm_decode_step, cfg=cfg,
            options=DecodeOptions(kernel_impl="sharded"), shard=shard))
        st_r = st_s = st
        t = tok
        for i in range(12):
            lg_r, st_r, _ = step_ref(params, st_r, t)
            lg_s, st_s, _ = step_sh(params, st_s, t)
            d = float(jnp.max(jnp.abs(lg_r.astype(jnp.float32)
                                      - lg_s.astype(jnp.float32))))
            assert d < 1e-3, f"step {i}: dlogit {d}"
            t = jnp.argmax(lg_r, -1).astype(jnp.int32)
        for name in ("k_cache", "v_cache", "kg_cache"):
            a, b = getattr(st_r, name), getattr(st_s, name)
            d = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                      - b.astype(jnp.float32))))
            assert d < 1e-3, f"{name}: {d}"
        assert np.array_equal(np.asarray(st_r.kg_n), np.asarray(st_s.kg_n))
    print("sharded_decode_parity OK")


def sharded_decode_threshold_parity():
    import dataclasses, functools
    import jax, jax.numpy as jnp
    import repro.configs as configs
    from repro.config import reduced
    from repro.core.policy import DecodeOptions
    from repro.data.pipeline import DataState, make_batch
    from repro.models import transformer as tf
    from repro.distributed import sharding as shd

    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = reduced(configs.get("qwen3_0_6b"))
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, method="threshold",
        threshold=2e-2, token_budget=256, local_cap_factor=8.0))
    params = tf.init_lm(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": make_batch(cfg, 4, 120, DataState(0, 0))["tokens"]}
    logits, st = tf.lm_prefill(params, batch, cfg, max_len=256)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    shard = shd.make_shard_fn(mesh)
    with mesh:
        step_ref = jax.jit(functools.partial(
            tf.lm_decode_step, cfg=cfg, options=DecodeOptions()))
        step_sh = jax.jit(functools.partial(
            tf.lm_decode_step, cfg=cfg,
            options=DecodeOptions(kernel_impl="sharded"), shard=shard))
        st_r = st_s = st
        t = tok
        for i in range(8):
            lg_r, st_r, _ = step_ref(params, st_r, t)
            lg_s, st_s, _ = step_sh(params, st_s, t)
            d = float(jnp.max(jnp.abs(lg_r.astype(jnp.float32)
                                      - lg_s.astype(jnp.float32))))
            assert d < 1e-3, f"step {i}: dlogit {d}"
            t = jnp.argmax(lg_r, -1).astype(jnp.int32)
    print("sharded_decode_threshold_parity OK")


def sharded_policy_golden():
    """DecodeOptions(kernel_impl='sharded') decode must be BITWISE equal
    to the pre-DecodeOptions sharded trajectory captured in
    tests/golden_policy.npz (capture_golden_policy.capture_sharded)."""
    import functools, os
    import jax, jax.numpy as jnp
    import numpy as np
    import capture_golden_policy as G
    from repro.core.policy import DecodeOptions
    from repro.data.pipeline import DataState, make_batch
    from repro.models import transformer as tf
    from repro.distributed import sharding as shd

    gold = np.load(os.path.join(os.path.dirname(__file__),
                                "golden_policy.npz"))
    G.check_versions(gold)
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = G.sharded_cfg()
    params = tf.init_lm(jax.random.PRNGKey(G.PARAM_SEED), cfg)
    batch = {"tokens": make_batch(cfg, G.SHARDED_B, G.SHARDED_PRE,
                                  DataState(0, 0))["tokens"]}
    logits, st = tf.lm_prefill(params, batch, cfg, max_len=G.SHARDED_MAX)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    shard = shd.make_shard_fn(mesh)
    lgs, tks = [], []
    with mesh:
        step = jax.jit(functools.partial(
            tf.lm_decode_step, cfg=cfg,
            options=DecodeOptions(kernel_impl="sharded"), shard=shard))
        for _ in range(G.N_STEPS):
            lg, st, aux = step(params, st, tok)
            tok = jnp.argmax(lg, -1).astype(jnp.int32)
            lgs.append(np.asarray(lg, np.float32))
            tks.append(np.asarray(tok, np.int32))
    np.testing.assert_array_equal(np.stack(tks), gold["sharded_tokens"])
    np.testing.assert_array_equal(np.stack(lgs), gold["sharded_logits"])
    assert 0.0 < float(aux["sparsity"]) < 1.0
    print("sharded_policy_golden OK")


def paged_sharded_parity():
    """Paged x sharded serving (ISSUE 4): the paged engine on a mesh with
    head-sharded pools must be BITWISE equal to the unsharded paged engine
    — same tokens, same logits — including under lazy admission with
    preemption, and close (not bitwise: cross-split reduction reorders the
    softmax) with split_k > 1."""
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    import repro.configs as configs
    from repro.config import reduced
    from repro.core.policy import DecodeOptions
    from repro.distributed import sharding as shd
    from repro.models.registry import get_api
    from repro.serve.engine import DecodeEngine

    mesh = make_mesh((4, 2), ("data", "model"))   # Hkv=2 over model=2
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=32))
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    specs = [(21, 8), (13, 10), (30, 6), (17, 7)]
    reqs = [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(specs)]

    eng_ref = DecodeEngine(cfg, params, max_len=64)
    res_ref = eng_ref.serve([dict(r) for r in reqs], n_slots=2,
                            collect_logits=True)

    shard = shd.make_shard_fn(mesh)
    with mesh:
        eng_sh = DecodeEngine(
            cfg, params, max_len=64, shard=shard,
            options=DecodeOptions(kernel_impl="sharded"))
        res_sh = eng_sh.serve([dict(r) for r in reqs], n_slots=2,
                              collect_logits=True)
        # tight pool: growth + preemption must survive the sharded path too
        res_pre = eng_sh.serve([dict(r) for r in reqs], n_slots=4,
                               num_pages=10, collect_logits=True)
        eng_sp = DecodeEngine(
            cfg, params, max_len=64, shard=shard,
            options=DecodeOptions(kernel_impl="sharded", split_k=2))
        res_sp = eng_sp.serve([dict(r) for r in reqs], n_slots=2,
                              collect_logits=True)
    assert res_pre["stats"]["preemptions"] > 0, res_pre["stats"]
    for r in reqs:
        rid = r["rid"]
        assert res_sh[rid] == res_ref[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(res_sh["logits"][rid],
                                      res_ref["logits"][rid])
        assert res_pre[rid] == res_ref[rid], f"rid {rid} preempt mismatch"
        np.testing.assert_array_equal(res_pre["logits"][rid],
                                      res_ref["logits"][rid])
        d = float(np.max(np.abs(res_sp["logits"][rid]
                                - res_ref["logits"][rid])))
        assert d < 1e-4, f"rid {rid} split_k=2 dlogit {d}"
    assert res_sh["stats"]["sparsity_by_rid"], "telemetry missing"
    print("paged_sharded_parity OK")


def paged_sharded_quant_parity():
    """Int8 page pools on the paged x sharded path (ISSUE 9): per-(page,
    head) scale rows shard over KV heads exactly like Kg (rank-3 spec on
    'model'), the fused dequant runs inside each head shard with zero
    per-step collectives, and the sharded int8 engine is BITWISE equal to
    the unsharded int8 engine — tokens and logits, including under a
    tight pool with preemption (swap round-trips the raw int8 + scales)."""
    import dataclasses
    import jax
    import numpy as np
    import repro.configs as configs
    from repro.config import reduced
    from repro.core.policy import DecodeOptions
    from repro.distributed import sharding as shd
    from repro.models.registry import get_api
    from repro.serve.engine import DecodeEngine

    mesh = make_mesh((4, 2), ("data", "model"))   # Hkv=2 over model=2
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=32))
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    specs = [(21, 8), (13, 10), (30, 6), (17, 7)]
    reqs = [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(specs)]

    eng_ref = DecodeEngine(cfg, params, max_len=64,
                           options=DecodeOptions(quantize="int8"))
    res_ref = eng_ref.serve([dict(r) for r in reqs], n_slots=2,
                            collect_logits=True)

    shard = shd.make_shard_fn(mesh)
    with mesh:
        eng_sh = DecodeEngine(
            cfg, params, max_len=64, shard=shard,
            options=DecodeOptions(kernel_impl="sharded", quantize="int8"))
        res_sh = eng_sh.serve([dict(r) for r in reqs], n_slots=2,
                              collect_logits=True)
        res_pre = eng_sh.serve([dict(r) for r in reqs], n_slots=4,
                               num_pages=10, collect_logits=True)
    assert res_pre["stats"]["preemptions"] > 0, res_pre["stats"]
    for r in reqs:
        rid = r["rid"]
        assert res_sh[rid] == res_ref[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(res_sh["logits"][rid],
                                      res_ref["logits"][rid])
        assert res_pre[rid] == res_ref[rid], f"rid {rid} preempt mismatch"
        np.testing.assert_array_equal(res_pre["logits"][rid],
                                      res_ref["logits"][rid])
    print("paged_sharded_quant_parity OK")


def paged_sharded_schedule_parity():
    """Step-level SelectionSchedule on the paged x sharded path (ISSUE 6):
    an all-select schedule (the dynamic plan machinery selecting at every
    layer) must be BITWISE equal to the static default, and a reuse
    schedule must be BITWISE equal to the same reuse schedule on the
    unsharded paged engine (the head-shard blend happens inside the shard
    body before the budget cap, preserving the paged==paged x sharded
    contract)."""
    import dataclasses
    import jax
    import numpy as np
    import repro.configs as configs
    from repro.config import reduced
    from repro.core.policy import DecodeOptions, SelectionSchedule
    from repro.distributed import sharding as shd
    from repro.models.registry import get_api
    from repro.serve.engine import DecodeEngine

    mesh = make_mesh((4, 2), ("data", "model"))   # Hkv=2 over model=2
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=32))
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    specs = [(21, 8), (13, 10), (30, 6)]
    reqs = [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(specs)]
    all_sel = SelectionSchedule(
        select_layer=0, correction_layers=tuple(range(1, cfg.num_layers)))
    reuse = SelectionSchedule(select_layer=0)
    shard = shd.make_shard_fn(mesh)

    def serve(options, sharded):
        eng = DecodeEngine(cfg, params, max_len=64, options=options,
                           shard=shard if sharded else None)
        return eng.serve([dict(r) for r in reqs], n_slots=2,
                         collect_logits=True)

    with mesh:
        base = serve(DecodeOptions(kernel_impl="sharded"), True)
        dyn = serve(DecodeOptions(kernel_impl="sharded", schedule=all_sel),
                    True)
        sh_reuse = serve(DecodeOptions(kernel_impl="sharded",
                                       schedule=reuse), True)
    local_reuse = serve(DecodeOptions(schedule=reuse), False)
    for r in reqs:
        rid = r["rid"]
        assert dyn[rid] == base[rid], f"rid {rid} all-select mismatch"
        np.testing.assert_array_equal(dyn["logits"][rid],
                                      base["logits"][rid])
        assert sh_reuse[rid] == local_reuse[rid], f"rid {rid} reuse"
        np.testing.assert_array_equal(sh_reuse["logits"][rid],
                                      local_reuse["logits"][rid])
    print("paged_sharded_schedule_parity OK")


def paged_sharded_eviction_parity():
    """RaaS page eviction on the paged x sharded decode path (ISSUE 7):
    a half-pool run with eviction on must be BITWISE equal to the ample
    sharded run — ghost-row gate metadata and the clamped K/V table
    behave identically when KV heads are sharded over the model axis."""
    import dataclasses
    import jax
    import numpy as np
    import repro.configs as configs
    from repro.config import reduced
    from repro.core.policy import DecodeOptions
    from repro.distributed import sharding as shd
    from repro.models.registry import get_api
    from repro.serve.engine import DecodeEngine
    from repro.serve.eviction import EvictionConfig

    mesh = make_mesh((4, 2), ("data", "model"))   # Hkv=2 over model=2
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=16))
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    specs = [(40, 25), (38, 24), (41, 22)]
    reqs = [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(specs)]
    shard = shd.make_shard_fn(mesh)
    opts = DecodeOptions(kernel_impl="sharded")
    with mesh:
        eng = DecodeEngine(cfg, params, max_len=128, options=opts,
                           shard=shard)
        ample = eng.serve([dict(r) for r in reqs], n_slots=3,
                          collect_logits=True)
        pool = 1 + (ample["stats"]["peak_pages_used"] + 1) // 2
        res = eng.serve([dict(r) for r in reqs], n_slots=3, num_pages=pool,
                        collect_logits=True, eviction=EvictionConfig())
    st = res["stats"]
    assert st["retired"] == len(reqs) and st["failed"] == 0, st["errors"]
    assert st["evictions"] > 0, st
    for r in reqs:
        rid = r["rid"]
        assert res[rid] == ample[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(res["logits"][rid],
                                      ample["logits"][rid])
    print("paged_sharded_eviction_parity OK")


def paged_sharded_hybrid_parity():
    """Hybrid family through the paged x sharded engine (ISSUE 10): the
    per-unit page pools ([n_units, P, Hkv, ps, Dh]) head-shard over
    'model' exactly like transformer pools and the per-slot recurrent
    state stays replicated (the engine never device_puts it; zero new
    per-step collectives). The sharded engine matches the unsharded one
    to rounding (tokens exact, logits <= 1e-4: GSPMD partitions the
    REPLICATED mamba matmuls differently under a mesh, so — unlike the
    pure-attention transformer, whose sharded math runs in an explicit
    shard_map — hybrid cross-engine logits are not bit-identical), and a
    tight-pool run with preemption is BITWISE equal to the same engine's
    ample run (the SwapEntry recurrent-state blob round-trips exactly)."""
    import jax
    import numpy as np
    import repro.configs as configs
    from repro.config import reduced
    from repro.core.policy import DecodeOptions
    from repro.distributed import sharding as shd
    from repro.models.registry import get_api
    from repro.serve.engine import DecodeEngine

    mesh = make_mesh((4, 2), ("data", "model"))   # Hkv=2 over model=2
    # num_layers=3 with period 2 -> 1 unit + 1 trailing mamba layer
    cfg = reduced(configs.get("zamba2_1_2b"),
                  num_layers=3).replace(dtype="float32")
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    specs = [(16, 8), (8, 10), (32, 6), (16, 7)]
    reqs = [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate(specs)]

    eng_ref = DecodeEngine(cfg, params, max_len=64)
    res_ref = eng_ref.serve([dict(r) for r in reqs], n_slots=2,
                            collect_logits=True)

    shard = shd.make_shard_fn(mesh)
    with mesh:
        eng_sh = DecodeEngine(
            cfg, params, max_len=64, shard=shard,
            options=DecodeOptions(kernel_impl="sharded"))
        res_sh = eng_sh.serve([dict(r) for r in reqs], n_slots=2,
                              collect_logits=True)
        # tight pool: growth + preemption must survive the sharded path
        # (recurrent rows captured/restored alongside the head-sharded
        # pages); same n_slots as the ample run so the comparison is
        # shape-identical and therefore bitwise
        res_amp = eng_sh.serve([dict(r) for r in reqs], n_slots=4,
                               collect_logits=True)
        res_pre = eng_sh.serve([dict(r) for r in reqs], n_slots=4,
                               num_pages=10, collect_logits=True)
    assert res_pre["stats"]["preemptions"] > 0, res_pre["stats"]
    assert res_amp["stats"]["preemptions"] == 0
    for r in reqs:
        rid = r["rid"]
        assert res_sh[rid] == res_ref[rid], f"rid {rid} token mismatch"
        d = float(np.max(np.abs(res_sh["logits"][rid]
                                - res_ref["logits"][rid])))
        assert d <= 1e-4, f"rid {rid} sharded dlogit {d}"
        assert res_pre[rid] == res_amp[rid], f"rid {rid} preempt mismatch"
        np.testing.assert_array_equal(res_pre["logits"][rid],
                                      res_amp["logits"][rid])
    print("paged_sharded_hybrid_parity OK")


def moe_sharded_parity():
    import dataclasses
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config import MoEConfig
    from repro.models import moe as moe_mod
    from repro.distributed import sharding as shd

    mesh = make_mesh((2, 4), ("data", "model"))
    D, E, K, F = 32, 8, 2, 64
    mcfg = MoEConfig(n_experts=E, top_k=K, n_shared_experts=1,
                     expert_d_ff=F, capacity_factor=8.0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), D, mcfg, "swiglu", "float32")
    shard = shd.make_shard_fn(mesh)
    mcfg2 = dataclasses.replace(mcfg, dispatch="shard_map")
    for t in (64, 8):   # big_t all-to-all path / small_t psum path
        x = jax.random.normal(jax.random.PRNGKey(1), (t, D), jnp.float32)
        y_ref, aux_ref = moe_mod.moe_mlp(p, x, mcfg, "swiglu", None)
        with mesh:
            xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
            y_sm, aux_sm = jax.jit(
                lambda xx: moe_mod.moe_mlp(p, xx, mcfg2, "swiglu", shard))(xs)
        assert float(jnp.max(jnp.abs(y_ref - y_sm))) < 1e-4, t
        assert abs(float(aux_ref) - float(aux_sm)) < 1e-5, t
    print("moe_sharded_parity OK")


def moe_sharded_grads():
    """Gradients flow through the explicit all-to-all dispatch."""
    import dataclasses
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config import MoEConfig
    from repro.models import moe as moe_mod
    from repro.distributed import sharding as shd

    mesh = make_mesh((2, 4), ("data", "model"))
    D, E, K, F = 32, 8, 2, 64
    mcfg = MoEConfig(n_experts=E, top_k=K, expert_d_ff=F, capacity_factor=8.0)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), D, mcfg, "swiglu", "float32")
    shard = shd.make_shard_fn(mesh)
    mcfg2 = dataclasses.replace(mcfg, dispatch="shard_map")
    x = jax.random.normal(jax.random.PRNGKey(1), (64, D), jnp.float32)

    def loss(x, mc, sh):
        y, aux = moe_mod.moe_mlp(p, x, mc, "swiglu", sh)
        return jnp.sum(y ** 2) + aux

    g_ref = jax.grad(lambda xx: loss(xx, mcfg, None))(x)
    with mesh:
        xs = jax.device_put(x, NamedSharding(mesh, P("data", "model")))
        g_sm = jax.jit(jax.grad(lambda xx: loss(xx, mcfg2, shard)))(xs)
    d = float(jnp.max(jnp.abs(g_ref - jax.device_get(g_sm))))
    assert d < 1e-4, d
    print("moe_sharded_grads OK")


def paged_sharded_rope_scaled():
    """Linear RoPE scaling on the paged x sharded path: the shard body's
    Kg finalize equals gate_k of the pre-RoPE keys under a x4 factor, and
    serving a 7:1-group scaled config on the mesh gives the unsharded
    engine's tokens and logits bitwise."""
    import functools
    import jax, jax.numpy as jnp
    import numpy as np
    import repro.configs as configs
    from repro.config import reduced
    from repro.core import attngate as ag
    from repro.core.policy import DecodeOptions
    from repro.distributed import sharding as shd
    from repro.models.common import apply_rope
    from repro.models.registry import get_api
    from repro.serve.engine import DecodeEngine
    from repro.serve.sharded import sharded_paged_decode

    mesh = make_mesh((4, 2), ("data", "model"))   # Hkv=2 over model=2
    cfg = reduced(configs.get("deepseek_coder_33b"), n_heads=14,
                  n_kv_heads=2).replace(dtype="float32")
    assert cfg.rope.factor == 4.0 and cfg.gqa_group == 7
    gcfg, rope = cfg.gate, cfg.rope
    ps, hkv, g = gcfg.block_size, cfg.n_kv_heads, cfg.gqa_group
    dh, dg, nb = cfg.resolved_head_dim, gcfg.d_gate, 5

    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    gate = ag.init_attngate(k1, n_kv_heads=hkv, group=g, head_dim=dh,
                            cfg=gcfg, dtype="float32")
    k_nope = jax.random.normal(k2, (1, nb * ps, hkv, dh), jnp.float32)
    want = np.asarray(ag.gate_k(gate, k_nope, gcfg))[0]
    k_pages = jnp.zeros((2, nb + 2, hkv, ps, dh), jnp.float32)
    v_pages = jnp.zeros_like(k_pages)
    kg_pages = jnp.zeros((2, nb + 2, hkv, dg), jnp.float32)
    table = jnp.asarray(1 + np.roll(np.arange(nb), 2)[None], jnp.int32)
    step = jax.jit(functools.partial(sharded_paged_decode, mesh=mesh,
                                     cfg=gcfg, rope=rope))
    with mesh:
        for t in range(nb * ps):
            kr = apply_rope(k_nope[:, t:t + 1], jnp.full((1, 1), t),
                            rope)[:, 0]
            _, k_pages, v_pages, kg_pages, _, _, _ = step(
                jnp.zeros((1, hkv, dg)), jnp.zeros((1, hkv, g, dh)), kr, kr,
                k_pages, v_pages, kg_pages, jnp.int32(1), table,
                jnp.full((1,), t, jnp.int32), jnp.ones((1,), bool),
                gate["wk"])
    got = np.asarray(kg_pages[1])[np.asarray(table[0])]
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(11)
    reqs = [{"rid": i, "max_new_tokens": mn,
             "tokens": rng.integers(0, cfg.vocab_size,
                                    size=(pl,)).astype(np.int32)}
            for i, (pl, mn) in enumerate([(41, 24), (57, 16), (30, 20)])]
    res_ref = DecodeEngine(cfg, params, max_len=96).serve(
        [dict(r) for r in reqs], n_slots=2, collect_logits=True)
    with mesh:
        eng_sh = DecodeEngine(cfg, params, max_len=96,
                              shard=shd.make_shard_fn(mesh),
                              options=DecodeOptions(kernel_impl="sharded"))
        res_sh = eng_sh.serve([dict(r) for r in reqs], n_slots=2,
                              collect_logits=True)
    for r in reqs:
        rid = r["rid"]
        assert res_sh[rid] == res_ref[rid], f"rid {rid} token mismatch"
        np.testing.assert_array_equal(res_sh["logits"][rid],
                                      res_ref["logits"][rid])
    print("paged_sharded_rope_scaled OK")


if __name__ == "__main__":
    globals()[sys.argv[1]]()
