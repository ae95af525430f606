"""The trace reducer: union busy time, idle share, device time by program
or kernel name, and gap attribution to host spans."""
from __future__ import annotations

import numpy as np
import pytest

from tinycell import DATA  # also puts bench/ on sys.path
from harness.trace import MODULES_LINE, OPS_LINE, Trace, merge


def _t(events):
    names = [e[0] for e in events]
    return (names, np.asarray([e[1] for e in events], np.int64),
            np.asarray([e[2] for e in events], np.int64))


def small_trace():
    # two decode steps of 100 ns with a nested pair of ops, a prefill,
    # and gaps the host spans explain
    ops = _t([("fusion.1", 0, 40), ("_kernel_paged", 40, 30),
              ("_select_paged_kernel", 35, 10),       # overlaps the kernel
              ("fusion.2", 200, 40), ("_kernel_paged", 240, 30),
              ("fusion.3", 420, 50)])
    mods = _t([("jit_lm_decode_step_paged(1)", 0, 70),
               ("jit_lm_decode_step_paged(1)", 200, 70),
               ("jit_lm_prefill(2)", 420, 50)])
    host = _t([("bench.pull", 80, 50), ("PjitFunction(x)", 75, 200),
               ("bench.on_token", 300, 20)])
    return Trace({"/device:TPU:0": {OPS_LINE: ops, MODULES_LINE: mods}},
                 host)


def test_merge_unions_overlaps():
    iv = merge(np.array([5, 0, 3, 20]), np.array([9, 4, 6, 25]))
    assert iv.tolist() == [[0, 9], [20, 25]]


def test_busy_counts_overlap_once():
    t = small_trace()
    assert t.busy_ns("/device:TPU:0") == 70 + 70 + 50


def test_time_by_program_and_kernel():
    t = small_trace()
    p = "/device:TPU:0"
    assert t.time_by(p, MODULES_LINE, lambda n: "decode_step" in n) \
        == (140, 2)
    assert t.time_by(p, OPS_LINE, lambda n: n == "_kernel_paged") == (60, 2)
    assert t.time_by(p, OPS_LINE, lambda n: "nothing" in n) == (0, 0)


def test_idle_gaps_named_after_host_spans():
    t = small_trace()
    gaps = t.idle_gaps("/device:TPU:0", 5)
    # 270..420 (bench.on_token covers 20 ns of it, the runtime event 5),
    # 70..200 (bench.pull preferred over the longer runtime event)
    assert gaps == [("bench.on_token", pytest.approx(150e-9)),
                    ("bench.pull", pytest.approx(130e-9))]


def test_top_ops_by_device_time():
    top = small_trace().top_ops(2)
    assert [n for n, _ in top] == ["_kernel_paged", "fusion.3"]
    assert top[0][1] == pytest.approx(60e-9)


def test_json_round_trip():
    t = small_trace()
    u = Trace.from_json(t.to_json())
    assert u.busy_ns("/device:TPU:0") == t.busy_ns("/device:TPU:0")
    assert u.idle_gaps("/device:TPU:0") == t.idle_gaps("/device:TPU:0")


def test_programs_told_apart_by_their_ops():
    t = small_trace()
    p = "/device:TPU:0"
    mask = t.modules_holding(p, lambda n: n == "_kernel_paged")
    assert mask.tolist() == [True, True, False]
    assert t.module_time(p, mask) == (140, 2)
    assert t.module_time(p, ~mask) == (50, 1)



def test_metric_readers_on_chip_names():
    """The trace metrics find the paged decode step and its kernels under
    the names a TPU v5e trace gives them: programs jitted from a partial
    are ``jit__unknown(<hash>)`` on the modules line, kernels are named by
    their HLO instruction (``%block_sparse_decode_paged.9``) on the ops
    line, nested in the layer loop (``%while.5``)."""
    import json
    import types
    from harness.cell import Run
    from harness.spec import Spec
    from tinycell import REPO
    spec = Spec(REPO)
    conf = json.loads((DATA / "tiny.json").read_text())
    ops, mods = [], []
    for t0 in (0, 200):                       # two decode steps, 2 layers
        ops += [("%while.5", t0, 100), ("%copy.192", t0, 5),
                ("%reduce.3", t0 + 110, 5)]
        for lo in (5, 45):
            ops += [("%fused_gate_select_paged.9", t0 + lo, 3),
                    ("%block_sparse_decode_paged.9", t0 + lo + 5, 20)]
        mods += [("jit__unknown(18402701480455327568)", t0, 100),
                 ("jit__argmax(7591354507478830871)", t0 + 110, 5)]
    trace = Trace({"/device:TPU:0": {OPS_LINE: _t(ops), MODULES_LINE: _t(mods)}},
                  _t([("bench.pull", 120, 70)]))

    def session(prompt, steps):
        return types.SimpleNamespace(prompt_len=prompt, times=[1.0, 2.0, 3.0],
                                     steps=steps, t_due=None)
    run = Run(spec=spec, conf=conf, mix={}, t_start=0.0, t_open=1.5,
              t_close=3.5, t_stop=3.5, n_slots=2, chips=1,
              sessions={0: session(40, [0, 1, 2]), 1: session(50, [0, 1, 2])},
              peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
              trace=trace, trace_window_s=400e-9)

    def metric(name):
        return spec.module("metrics", name).reduce(run)
    assert metric("decode_step_ms") == pytest.approx(100e-6)
    assert metric("device_idle_share") == pytest.approx(100 * (1 - 210 / 400))
    for name, work, ns in (("sparse_attn_roofline",
                            "block_sparse_decode_paged", 80),
                           ("gate_select_roofline",
                            "fused_gate_select_paged", 12)):
        w = spec.module("work", work).work
        least = sum(max(f / 1e12, b / 1e9) for f, b in
                    (w(conf, [41, 51]), w(conf, [42, 52])))
        assert metric(name) == pytest.approx(
            100 * conf["num_hidden_layers"] * least / (ns * 1e-9))
