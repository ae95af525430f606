"""Unit tests of the benchmark's arithmetic: work counts, traffic,
window statistics. CPU only, no model."""
from __future__ import annotations

import json
import types

import numpy as np
import pytest

import tinycell  # noqa: F401  (puts bench/ on sys.path)
from harness.spec import Spec
from harness.window import decode_steps, gaps, quantile, ttfts, window_tokens

SPEC = Spec(tinycell.REPO)
CONF = json.loads((tinycell.DATA / "tiny.json").read_text())


def test_attended_tokens_by_hand():
    from harness.work_common import attended
    # block 8, budget 4 blocks: 4 or fewer visible blocks -> every token
    assert attended(1, 8, 4) == 1
    assert attended(32, 8, 4) == 32
    # 5 visible blocks (33..40 tokens): 3 whole blocks + the valid tail
    assert attended(33, 8, 4) == 3 * 8 + 1
    assert attended(40, 8, 4) == 3 * 8 + 8
    assert attended(1000, 8, 4) == 3 * 8 + 1000 - 124 * 8


def test_block_sparse_work_by_hand():
    work = SPEC.module("work", "block_sparse_decode_paged").work
    # tiny: 4 q heads, 2 kv heads, head_dim 16, f32 (4 bytes), block 8, k 4
    flops, nbytes = work(CONF, [33])
    att = 25
    assert flops == 4 * 4 * 16 * att
    assert nbytes == (2 * att * 2 * 16 + 2 * 4 * 16) * 4
    f2, b2 = work(CONF, [33, 33])
    assert (f2, b2) == (2 * flops, 2 * nbytes)


def test_gate_select_work_by_hand():
    work = SPEC.module("work", "fused_gate_select_paged").work
    flops, nbytes = work(CONF, [33])       # 5 visible blocks
    assert flops == 2 * 2 * 16 * 5
    assert nbytes == (5 + 1) * 2 * 16 * 4 + 2 * 4 * 4


def test_decode_flops_by_hand():
    ds = SPEC.module("work", "decode_step")
    d, ff, layers, h, kv, dh, v, dg = 64, 128, 2, 4, 2, 16, 256, 16
    per_layer = 2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff + h * dh * dg
    assert ds.matmul_params(CONF) == layers * per_layer + d * v
    f = ds.flops_per_token(CONF, 33)
    assert f == 2 * (layers * per_layer + d * v) + layers * (
        4 * h * dh * 25 + 2 * kv * dg * 5)


def _mix():
    return json.loads((tinycell.DATA / "tiny_mix.json").read_text())


def _requests(seed):
    t = SPEC.module("traffic", "closed_loop").Traffic(_mix(), seed, 256)
    reqs = t.initial()
    for c in range(t.clients):
        for _ in range(t.mix["grid"]):          # one whole cycle
            t.finished(c, 0.0)
    return reqs + t.due(1.0)


def test_traffic_same_seed_same_requests():
    a, b = _requests(2 ** 31 + 7), _requests(2 ** 31 + 7)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["max_new_tokens"] == y["max_new_tokens"]
        assert np.array_equal(x["tokens"], y["tokens"])


def test_traffic_seeds_share_sizes_not_order():
    a, b = _requests(11), _requests(12)
    for key in (lambda r: len(r["tokens"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert [len(r["tokens"]) for r in a] != [len(r["tokens"]) for r in b] \
        or not all(np.array_equal(x["tokens"], y["tokens"])
                   for x, y in zip(a, b))


def test_traffic_due_only_after_finish():
    t = SPEC.module("traffic", "closed_loop").Traffic(_mix(), 5, 256)
    t.initial()
    assert t.due(10.0) == []
    t.finished(1, 2.0)
    assert t.due(1.0) == []
    (r,) = t.due(2.0)
    assert r["client"] == 1 and r["due"] == 2.0


def _session(times, t_due=None, prompt=10, steps=None):
    return types.SimpleNamespace(times=list(times), t_due=t_due,
                                 prompt_len=prompt,
                                 steps=steps or list(range(len(times))))


def test_quantile_linear_between_order_statistics():
    assert quantile([1, 2, 3, 4, 5], 0.5) == 3
    assert quantile([1, 2, 3, 4, 5], 0.95) == pytest.approx(4.8)
    assert quantile([7], 0.9) == 7
    with pytest.raises(ValueError):
        quantile([], 0.5)


def test_window_gaps_tokens_and_ttft():
    run = types.SimpleNamespace(t_open=10.0, t_close=20.0, sessions={
        0: _session([9.0, 10.5, 11.0, 19.5, 20.5]),
        1: _session([12.0, 13.0], t_due=11.0),
        2: _session([], t_due=18.0),            # no token by the close
        3: _session([25.0], t_due=21.0),        # due after the close
    })
    # tokens the host had inside [10, 20]
    assert len(window_tokens(run)) == 5
    # a gap counts only when both tokens are in the window
    assert sorted(gaps(run)) == pytest.approx([0.5, 1.0, 8.5])
    # 1 waited 1.0 s; 2 has no first token and counts its 2.0 s of waiting
    assert sorted(ttfts(run)) == pytest.approx([1.0, 2.0])


def test_decode_steps_skip_prefill_tokens():
    run = types.SimpleNamespace(t_open=0.0, t_close=10.0, sessions={
        0: _session([1.0, 2.0, 3.0], steps=[4, 5, 6]),
        1: _session([2.0, 3.0], steps=[5, 6]),
    })
    steps = decode_steps(run)
    assert sorted(steps) == [5, 6]
    assert len(steps[5]) == 1 and len(steps[6]) == 2


def test_bench_keeps_its_own_yardstick():
    """Nothing in bench/ imports the program's traffic or latency code,
    and the references import nothing of the program at all."""
    for path in tinycell.BENCH.rglob("*.py"):
        if "tests" in path.parts:
            continue
        text = path.read_text()
        assert "repro.serve.traffic" not in text, path
        assert "repro.serve.frontend" not in text, path
    for path in (tinycell.BENCH / "reference").glob("*.py"):
        assert "repro" not in path.read_text(), path
