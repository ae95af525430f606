"""Ahead-of-time TPU compiles of the decode-path Pallas kernels.

Interpret mode (the rest of the suite) cannot see the TPU's own limits:
the (8, 128) block-tiling rule, scalar-memory (SMEM) and VMEM budgets.
These tests hand each main-path kernel to the TPU compiler for a
DESCRIBED v5e chip at the full qwen3_0_6b decode widths — no chip is
needed, nothing runs, and each compile takes about two seconds. The
topology is described inside a module fixture (never at import time), so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU compiler. The paged x sharded layer
body compiles over all four chips of the described v5e:2x2, with the
Pallas kernels inside its ``shard_map``.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as PS_
from jax.sharding import SingleDeviceSharding

import repro.configs as configs
from repro.config import Rope
from repro.kernels.block_sparse_decode import (
    block_sparse_decode, block_sparse_decode_paged,
    block_sparse_decode_paged_splitk)
from repro.kernels.gate_select import (fused_gate_select,
                                       fused_gate_select_paged)
from repro.launch.mesh import make_mesh
from repro.serve.sharded import sharded_paged_decode

# qwen3_0_6b decode widths: 8 slots, 8 KV heads x GQA group 2, head_dim
# 128, page == gate block 64, d_gate 128; a 2048-page pool stacked over 4
# layers (read at a layer index), 512-entry page tables (32k-token
# contexts) and the config's 64-block budget
GATE = configs.get("qwen3_0_6b").gate
B, HKV, G, DH, PS, DG = 8, 8, 2, 128, GATE.block_size, GATE.d_gate
L, P, NPT, K = 4, 2048, 512, GATE.token_budget // GATE.block_size


@pytest.fixture(scope="module")
def topology():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topology):
    return SingleDeviceSharding(topology.devices[0])


@pytest.fixture(scope="module")
def four_chips(topology):
    """The (1, 4) ("data", "model") mesh of the paged x sharded path."""
    return make_mesh((1, 4), ("data", "model"), devices=topology.devices)


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the Mosaic kernel is in there


def _paged_operands(one_chip, kv_dtype):
    return (_sds(one_chip, (B, HKV, G, DH), jnp.bfloat16),
            _sds(one_chip, (L, P, HKV, PS, DH), kv_dtype),
            _sds(one_chip, (L, P, HKV, PS, DH), kv_dtype),
            _sds(one_chip, (), jnp.int32),
            _sds(one_chip, (B, HKV, K), jnp.int32),
            _sds(one_chip, (B, NPT), jnp.int32),
            _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_block_sparse_decode_paged_compiles(one_chip, quant):
    def fn(q, kp, vp, layer, idx, pt, kv_len, *scales):
        ks, vs = scales or (None, None)
        return block_sparse_decode_paged(q, kp, vp, layer, idx, pt, kv_len,
                                         block_size=PS, k_scales=ks,
                                         v_scales=vs)
    scales = ((_sds(one_chip, (L, P, HKV, 1), jnp.float32),) * 2 if quant
              else ())
    _compile(fn, *_paged_operands(one_chip,
                                  jnp.int8 if quant else jnp.bfloat16),
             *scales)


def test_block_sparse_decode_paged_compiles_at_a_7_to_1_group(one_chip):
    """deepseek_coder_33b_pp16.long8_16k's call: 56/8 heads (a 7:1 group,
    padded to the sublanes in the kernel), 4 layers of a 2049-page pool,
    256-entry tables (16384 positions) and a 64-block budget."""
    g, p, npt, k = 7, 2049, 256, 64

    def fn(q, kp, vp, layer, idx, pt, kv_len):
        return block_sparse_decode_paged(q, kp, vp, layer, idx, pt, kv_len,
                                         block_size=PS)
    pool = _sds(one_chip, (L, p, HKV, PS, DH), jnp.bfloat16)
    _compile(fn, _sds(one_chip, (B, HKV, g, DH), jnp.bfloat16), pool, pool,
             _sds(one_chip, (), jnp.int32), _sds(one_chip, (B, HKV, k),
                                                 jnp.int32),
             _sds(one_chip, (B, npt), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


def test_block_sparse_decode_paged_splitk_compiles(one_chip):
    def fn(q, kp, vp, layer, idx, pt, kv_len):
        return block_sparse_decode_paged_splitk(q, kp, vp, layer, idx, pt,
                                                kv_len, block_size=PS,
                                                num_splits=2)
    _compile(fn, *_paged_operands(one_chip, jnp.bfloat16))


def test_block_sparse_decode_compiles(one_chip):
    def fn(q, kc, vc, idx, kv_len):
        return block_sparse_decode(q, kc, vc, idx, kv_len, block_size=PS)
    cache = _sds(one_chip, (B, HKV, NPT * PS, DH), jnp.bfloat16)
    _compile(fn, _sds(one_chip, (B, HKV, G, DH), jnp.bfloat16), cache, cache,
             _sds(one_chip, (B, HKV, K), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("method", ["budget", "threshold"])
def test_fused_gate_select_compiles(one_chip, method):
    cfg = dataclasses.replace(GATE, method=method)

    def fn(qg, kg, n_valid):
        return fused_gate_select(qg, kg, n_valid, cfg)
    _compile(fn, _sds(one_chip, (B, HKV, DG), jnp.bfloat16),
             _sds(one_chip, (B, HKV, NPT, DG), jnp.bfloat16),
             _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("method", ["budget", "threshold"])
def test_fused_gate_select_paged_compiles(one_chip, method, dtype):
    """Kg pools hold the model dtype (bf16 at serving width, f32 in the
    reduced test configs)."""
    cfg = dataclasses.replace(GATE, method=method)

    def fn(qg, kg_pages, pt, n_valid):
        return fused_gate_select_paged(qg, kg_pages, pt, n_valid, cfg)
    _compile(fn, _sds(one_chip, (B, HKV, DG), dtype),
             _sds(one_chip, (P, HKV, DG), dtype),
             _sds(one_chip, (B, NPT), jnp.int32),
             _sds(one_chip, (B,), jnp.int32))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_sharded_paged_decode_compiles(four_chips, quant):
    """One layer's paged x sharded step (append, gate-select, block-sparse
    attention) on the stacked pools at a layer index, with the Pallas
    kernels inside its ``shard_map``, whose
    replication check stays on: each kernel output declares the mesh axes
    it varies over."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(four_chips, PS_()))
    kv = jnp.int8 if quant else jnp.bfloat16
    args = (sds((B, HKV, DG), jnp.bfloat16), sds((B, HKV, G, DH), jnp.bfloat16),
            sds((B, HKV, DH), jnp.bfloat16), sds((B, HKV, DH), jnp.bfloat16),
            sds((L, P, HKV, PS, DH), kv), sds((L, P, HKV, PS, DH), kv),
            sds((L, P, HKV, DG), jnp.bfloat16), sds((), jnp.int32),
            sds((B, NPT), jnp.int32), sds((B,), jnp.int32),
            sds((B,), jnp.bool_), sds((HKV, 3 * DH, DG), jnp.bfloat16))
    scales = (sds((L, P, HKV, 1), jnp.float32),) * 2 if quant else ()

    def fn(*a):
        ks, vs = a[12:] or (None, None)
        return sharded_paged_decode(*a[:12], mesh=four_chips, cfg=GATE,
                                    rope=Rope(1e6), inner_impl="pallas",
                                    k_scale=ks, v_scale=vs)
    _compile(fn, *args, *scales)
