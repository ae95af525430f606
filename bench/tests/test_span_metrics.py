"""The readers of the program's own host spans: host_turn_ms and
host_syncs_per_step, on a hand-built trace and on a traced run of the
tiny cell."""
from __future__ import annotations

import json

import numpy as np
import pytest

import tinycell  # also puts bench/ on sys.path
from harness import spans
from harness import trace as tr
from harness.trace import Trace


def _host(events):
    return ([e[0] for e in events],
            np.asarray([e[1] for e in events], np.int64),
            np.asarray([e[2] - e[1] for e in events], np.int64))


def hand_trace():
    """Host events (name, start ns, end ns) of four loop iterations."""
    return Trace({}, _host([
        # the tail of a step whose span began before the trace: its
        # children are recorded, the step span is not
        ("serve.dispatch", 0, 10), ("serve.sync", 12, 20),
        ("serve.sync", 22, 30),
        # a decode step: 4 syncs (two of them back to back), 35 ns waited
        ("serve.step", 100, 200), ("serve.arrivals", 100, 105),
        ("bench.pull", 101, 104), ("serve.dispatch", 110, 120),
        ("PjitFunction(paged_decode_step)", 111, 119),
        ("serve.sync", 130, 140), ("serve.sync", 140, 150),
        ("serve.sync", 150, 160), ("serve.sync", 165, 170),
        ("serve.complete", 175, 190), ("bench.on_token", 176, 180),
        # a decode step with an admission: its prefill's pull is a fifth
        ("serve.step", 300, 420), ("serve.admit", 305, 340),
        ("serve.sync", 330, 338), ("serve.dispatch", 345, 350),
        ("serve.sync", 360, 370), ("serve.sync", 370, 372),
        ("serve.sync", 380, 390), ("serve.sync", 395, 400),
        # an iteration that held no dispatch (the window closed in pull)
        ("serve.step", 500, 520), ("serve.arrivals", 500, 519),
        ("serve.sync", 505, 510),
    ]))


def test_readers_on_a_hand_built_trace():
    t = hand_trace()
    assert spans.decode_steps(t).tolist() == [[100, 200], [300, 420]]
    dur, count, waited = spans.per_step(t)
    assert dur.tolist() == [100, 120]
    assert count.tolist() == [4, 5]
    assert waited.tolist() == [35, 35]
    assert spans.host_turn_ms(t) == pytest.approx((65 + 85) / 2 * 1e-6)
    assert spans.syncs_per_step(t) == pytest.approx(4.5)


def test_readers_find_nothing_without_serve_spans():
    t = Trace({}, _host([("bench.pull", 0, 50), ("bench.on_token", 60, 70),
                         ("PjitFunction(x)", 5, 40)]))
    assert len(spans.decode_steps(t)) == 0
    assert spans.host_turn_ms(t) is None
    assert spans.syncs_per_step(t) is None


@pytest.mark.parametrize("name", ["host_turn_ms", "host_syncs_per_step"])
def test_metric_files_read_the_spans(name):
    from harness.cell import Run
    from harness.spec import Spec
    spec = Spec(tinycell.REPO)
    run = Run(spec=spec, conf={}, mix={}, sessions={}, t_start=0.0,
              t_open=0.0, t_close=1.0, t_stop=1.0, n_slots=2, chips=1,
              peaks={}, trace=hand_trace(), trace_window_s=1e-6)
    want = {"host_turn_ms": 75e-6, "host_syncs_per_step": 4.5}[name]
    reduce = spec.module("metrics", name).reduce
    assert reduce(run) == pytest.approx(want)
    assert reduce(Run(**{**run.__dict__, "trace": None})) is None


def test_tiny_cell_reports_both_metrics_traced(tmp_path, monkeypatch):
    """A traced CPU run of the tiny cell through bench/run.py reports both
    metrics; every decode step without an admission pulls 4 times
    (isfinite, argmax, the two sparsity rows)."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    root = tinycell.make_root(tmp_path)
    doc = json.loads((root / "BENCHMARK.json").read_text())
    for m in doc["per_layer"]:
        if m["name"] in ("host_turn_ms", "host_syncs_per_step"):
            m["workloads"].append(tinycell.TINY_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    rc, res, err = tinycell.run_tiny(root, 2 ** 31 + 99, trace=1)
    assert rc == 0, err
    m = res["metrics"]
    assert m["host_turn_ms"]["value"] > 0
    assert m["host_turn_ms"]["unit"] == "ms"
    assert m["host_syncs_per_step"]["value"] >= 4.0
    t = tr.read(str(root / "bench_out" / "trace" / tinycell.TINY_CELL))
    steps = spans.decode_steps(t)
    _, count, _ = spans.per_step(t)
    names, st, du = t.host
    admits = [s for n, s in zip(names, st) if n == "serve.admit"]
    plain = [c for (a, b), c in zip(steps, count)
             if not any(a <= s < b for s in admits)]
    assert len(plain) > 10
    assert set(plain) == {4}
