"""Host spans of ``DecodeEngine.serve`` in the profiler's own trace.

A served run under ``jax.profiler.trace`` writes one ``serve.step`` span
per loop iteration, with the admission, dispatch and every blocking
device-to-host pull (``serve.sync``) inside it; with no trace active the
spans do nothing and the tokens are the same.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.config import reduced
from repro.core.policy import DecodeOptions
from repro.models.registry import get_api
from repro.serve.engine import DecodeEngine

jax.config.update("jax_platform_name", "cpu")

# two requests, two slots: both admitted up front, no arrivals, no faults
SPECS = [(21, 6), (13, 4)]


def _engine(**options):
    cfg = reduced(configs.get("qwen3_0_6b")).replace(dtype="float32")
    cfg = cfg.replace(gate=dataclasses.replace(
        cfg.gate, block_size=8, d_gate=16, token_budget=16))
    params = get_api(cfg).init_params(jax.random.PRNGKey(0), cfg)
    return DecodeEngine(cfg, params, max_len=64,
                        options=DecodeOptions(**options))


def _requests(eng):
    rng = np.random.default_rng(0)
    return [{"rid": i, "max_new_tokens": new,
             "tokens": rng.integers(0, eng.cfg.vocab_size, size=(n,))
             .astype(np.int32)} for i, (n, new) in enumerate(SPECS)]


def _serve_traced(eng, log_dir):
    """(result, [(name, start ns, end ns, args)] of the serve.* spans)."""
    from jax._src.profiler import ProfileData
    with jax.profiler.trace(str(log_dir)):
        res = eng.serve(_requests(eng), n_slots=2)
    path, = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return res, sorted(spans, key=lambda s: s[1])


def _step_of(steps, span):
    """Index of the step span that holds ``span`` wholly, or None."""
    for i, (_, a, b, _) in enumerate(steps):
        if a <= span[1] and span[2] <= b:
            return i
    return None


def test_serve_spans_nest_in_one_step_span_per_decode_step(tmp_path):
    eng = _engine()
    plain = eng.serve(_requests(eng), n_slots=2)
    res, spans = _serve_traced(eng, tmp_path)
    steps = [s for s in spans if s[0] == "serve.step"]
    assert len(steps) == res["stats"]["decode_steps"] > 0
    assert [s[3]["step_num"] for s in steps] == list(range(len(steps)))
    inner = [s for s in spans
             if s[0] in ("serve.sync", "serve.dispatch", "serve.admit")]
    assert {s[0] for s in inner} == {"serve.sync", "serve.dispatch",
                                     "serve.admit"}
    assert all(_step_of(steps, s) is not None for s in inner)
    admits = [s for s in spans if s[0] == "serve.admit"]
    assert sorted(s[3]["rid"] for s in admits) == [0, 1]
    assert all(s[3]["prompt_len"] == SPECS[s[3]["rid"]][0] for s in admits)
    # the spans change nothing: same tokens as the untraced serve
    for rid in range(len(SPECS)):
        assert res[rid] == plain[rid]


@pytest.mark.parametrize("measure_sparsity", [True, False])
def test_serve_syncs_per_greedy_step(tmp_path, measure_sparsity):
    """A greedy step with no admission makes one blocking pull, the step
    program's packed output (``what="step_out"``: greedy tokens, finite
    flags and, with telemetry on, the sparsity rows), in one serve.sync
    span, whether or not telemetry is on."""
    eng = _engine(measure_sparsity=measure_sparsity)
    _, spans = _serve_traced(eng, tmp_path)
    steps = [s for s in spans if s[0] == "serve.step"]
    count = [0] * len(steps)
    admitted = [False] * len(steps)
    for s in spans:
        if s[0] in ("serve.sync", "serve.admit"):
            i = _step_of(steps, s)
            if s[0] == "serve.sync":
                count[i] += 1
                assert s[3]["what"] in ("step_out", "prefill_logits")
            else:
                admitted[i] = True
    plain = [c for c, a in zip(count, admitted) if not a]
    assert len(plain) == len(steps) - 1       # both admitted at step 0
    assert plain == [1] * len(plain)
    # the admission step adds one pull of each prefill's logits
    assert count[0] == 1 + len(SPECS)


@pytest.mark.parametrize("measure_sparsity", [True, False])
def test_one_pull_matches_host_argmax_and_telemetry(measure_sparsity):
    """The tokens and sparsity read from the step's packed output equal
    what the host makes of the pulled logits, and an injected "logits"
    fault still retires exactly one row."""
    from repro.serve.faults import FaultInjector
    eng = _engine(measure_sparsity=measure_sparsity)
    plain = eng.serve(_requests(eng), n_slots=2)
    pulled = eng.serve(_requests(eng), n_slots=2, collect_logits=True)
    for rid in range(len(SPECS)):
        assert plain[rid] == pulled[rid]
        assert len(plain[rid]) == SPECS[rid][1]
        np.testing.assert_array_equal(
            np.argmax(pulled["logits"][rid], axis=-1), plain[rid])
    for key in ("sel_blocks_by_rid", "sparsity_by_rid"):
        assert plain["stats"][key] == pulled["stats"][key]
        assert len(plain["stats"][key]) == (len(SPECS) if measure_sparsity
                                            else 0)
    # the second decode step's first active row (rid 0) reads non-finite
    res = eng.serve(_requests(eng), n_slots=2,
                    faults=FaultInjector({"logits": [1]}))
    assert res["stats"]["errors"] == {0: "non_finite_logits"}
    assert res["stats"]["failed"] == 1
    # retired after its prefill token and the first decode step's
    assert res[0] == plain[0][:2]
    assert res[1] == plain[1]
