"""Distribution-layer tests.

Multi-device shard_map parity runs in subprocesses (8 forced host devices;
the pytest process itself stays single-device). Sharding-rule unit tests
run in-process with abstract meshes.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

HERE = os.path.dirname(__file__)
SRC = os.path.join(HERE, "..", "src")


def _run(name: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "sharded_helpers.py"), name],
        capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"{name} failed:\n{r.stdout}\n{r.stderr}"
    assert f"{name} OK" in r.stdout


def test_sharded_decode_parity():
    _run("sharded_decode_parity")


def test_sharded_decode_threshold_parity():
    _run("sharded_decode_threshold_parity")


def test_paged_sharded_parity():
    """ISSUE 4 acceptance: the paged engine on a sharded mesh (pools
    head-sharded, page table replicated) is BITWISE equal to the unsharded
    paged engine — also under preemption — and split_k=2 stays within
    rounding."""
    _run("paged_sharded_parity")


def test_paged_sharded_quant_parity():
    """ISSUE 9 acceptance: int8 pools on the paged x sharded path — scale
    rows head-sharded like Kg, fused dequant inside each shard — stay
    BITWISE equal to the unsharded int8 engine, also under preemption."""
    _run("paged_sharded_quant_parity")


def test_paged_sharded_eviction_parity():
    """ISSUE 7 acceptance: page eviction at ~half pool on the sharded
    paged engine stays bitwise equal to the ample sharded run."""
    _run("paged_sharded_eviction_parity")


def test_paged_sharded_hybrid_parity():
    """ISSUE 10 acceptance: the hybrid family through the paged x sharded
    engine — per-unit pools head-sharded, recurrent slot state replicated
    — matches the unsharded hybrid engine (tokens exact, logits to
    rounding) and preempt/swap/resume stays bitwise vs the same engine's
    ample run."""
    _run("paged_sharded_hybrid_parity")


def test_moe_sharded_parity():
    _run("moe_sharded_parity")


def test_moe_sharded_grads():
    _run("moe_sharded_grads")


# ---------------------------------------------------------------------------
# sharding rules (in-process, abstract mesh)
# ---------------------------------------------------------------------------

def _mesh1():
    from repro.launch.mesh import make_local_mesh
    return make_local_mesh()


def test_sanitize_spec_drops_nondivisible():
    from jax.sharding import Mesh
    import numpy as np
    from repro.distributed.sharding import sanitize_spec
    devs = np.array(jax.devices() * 1).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    # model axis size 1 divides everything -> spec unchanged
    assert sanitize_spec(P("model", None), (504, 128), mesh) == P("model")
    # fake a 16-way axis via a mesh-shape shim
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    assert sanitize_spec(P("model", None), (504, 128), FakeMesh()) == P()
    assert sanitize_spec(P("model", None), (512, 128), FakeMesh()) == P("model")
    assert sanitize_spec(P(None, ("data", "model")), (5, 512), FakeMesh()) \
        == P(None, ("data", "model"))
    assert sanitize_spec(P(None, ("data", "model")), (5, 100), FakeMesh()) == P()


def test_paged_pool_pspecs_head_sharded():
    """Paged x sharded composition rule: pools shard Hkv on 'model'
    (axis 2), Kg pools likewise; non-divisible head counts fall back to
    replication on that axis only."""
    import numpy as np
    from repro.distributed.sharding import paged_pool_pspecs
    from repro.serve.paging import PagedPages

    class FakeMesh:
        shape = {"data": 2, "model": 2}
        axis_names = ("data", "model")

    pages = PagedPages(
        k_pages=jnp.zeros((2, 5, 4, 8, 16)),
        v_pages=jnp.zeros((2, 5, 4, 8, 16)),
        kg_pages=jnp.zeros((2, 5, 4, 16)))
    specs = paged_pool_pspecs(pages, FakeMesh())
    # sanitize_spec strips trailing Nones — same partitioning
    assert specs.k_pages == P(None, None, "model")
    assert specs.v_pages == P(None, None, "model")
    assert specs.kg_pages == P(None, None, "model")
    odd = pages._replace(k_pages=jnp.zeros((2, 5, 3, 8, 16)))
    assert paged_pool_pspecs(odd, FakeMesh()).k_pages == P()
    none_kg = pages._replace(kg_pages=None)
    assert paged_pool_pspecs(none_kg, FakeMesh()).kg_pages is None


def test_decode_partition_matches_state_specs():
    from repro.distributed.sharding import decode_partition
    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")
    bspec, seq = decode_partition(FakeMesh(), 128)
    assert bspec == "data" and seq == ("model",)
    bspec, seq = decode_partition(FakeMesh(), 1)     # long_500k
    assert bspec is None and seq == ("data", "model")
