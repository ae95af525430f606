"""The deepseek_coder_33b_pp16 cell's files against the program, and the
whole-step HBM share it reports (``bench/work/decode_step_bytes.py``,
``bench/metrics/decode_hbm_share.py``). CPU only."""
from __future__ import annotations

import dataclasses
import json
import sys
import types
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "bench" / "tests"))

import tinycell  # noqa: E402  (puts bench/ on sys.path)
from harness.model import param_shapes, program_config  # noqa: E402
from harness.spec import Spec  # noqa: E402

import repro.configs as configs  # noqa: E402
from repro.serve.scheduler import pages_needed  # noqa: E402

SPEC = Spec(REPO)
CELL = "deepseek_coder_33b_pp16.long8_16k"
CONF = SPEC.config("deepseek_coder_33b_pp16")
MIX = SPEC.mix("long8_16k")


def test_config_file_ties_to_program_rope():
    """The keys the harness does not map onto ModelConfig: the program's
    deepseek_coder_33b carries the file's RoPE scaling and context, so the
    program and the reference read the same RoPE."""
    base = configs.get(CONF["program_base"])
    assert CONF["rope_scaling"] == dataclasses.asdict(base.rope_scaling)
    assert CONF["max_position_embeddings"] == base.max_position_embeddings
    cfg = program_config(CONF)
    assert cfg.rope_scaling == base.rope_scaling
    assert cfg.rope.factor == CONF["rope_scaling"]["factor"] == 4.0
    ref = SPEC.module("reference", CONF["reference"])
    assert ref.Dims.of(CONF).rope_factor == 4.0
    # the gate's own RoPE stays unscaled at its base
    assert cfg.gate.rope.factor == 1.0
    assert cfg.gate.rope_theta == CONF["gate"]["rope_theta"] == 10000.0


def test_config_reduces_only_depth():
    entry = next(c for c in SPEC.doc["configs"]
                 if c["name"] == "deepseek_coder_33b_pp16")
    assert entry["reduced"] == list(CONF["reduced"]) == ["num_hidden_layers"]
    assert CONF["num_hidden_layers"] == 4
    base = configs.get(CONF["program_base"])
    cfg = program_config(CONF)
    for field in ("d_model", "d_ff", "n_heads", "n_kv_heads", "head_dim",
                  "vocab_size", "rope_theta", "norm_eps", "tie_embeddings"):
        assert getattr(cfg, field) == getattr(base, field), field


def test_mix_lifetimes_reach_published_context():
    cell = SPEC.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("deepseek_coder_33b_pp16", "long8_16k", 1)
    hi = MIX["prompt_tokens"][1] + MIX["new_tokens"][1]
    assert hi == CONF["max_position_embeddings"] == 16384
    ps = CONF["gate"]["block_size"]
    assert MIX["pool_pages"] == MIX["clients"] * pages_needed(
        MIX["prompt_tokens"][1], MIX["new_tokens"][1], ps) + 1 == 2049
    assert MIX["sampling"] is None and MIX["clients"] == 8


def _tree_params(conf):
    """(every parameter, the embedding table's) of the program's tree."""
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_flatten_with_path(
        param_shapes(program_config(conf)))[0]
    total = sum(int(np.prod(x.shape)) for _, x in leaves)
    embed = sum(int(np.prod(x.shape)) for p, x in leaves
                if getattr(p[0], "key", None) == "embed")
    return total, embed


@pytest.mark.parametrize("config", ["qwen3_0_6b", "deepseek_coder_33b_pp16"])
def test_step_weights_are_the_program_tree(config):
    """Every weight the step reads whole, counted from the file, is the
    program's parameter tree less the untied embedding table (a tied one is
    the head); the file's weight_bytes is the whole tree."""
    conf = SPEC.config(config)
    total, embed = _tree_params(conf)
    whole = total - (0 if conf["tie_word_embeddings"] else embed)
    assert SPEC.module("work", "decode_step_bytes").weight_params(conf) \
        == whole
    assert conf["weight_bytes"] == 2 * total


def test_step_bytes_by_hand():
    conf = json.loads((tinycell.DATA / "tiny.json").read_text())
    wb = SPEC.module("work", "decode_step_bytes")
    # tiny: d 64, ff 128, 2 layers, 4/2 heads x 16, vocab 256 tied,
    # qk-norm, d_gate 16, block 8, budget 4 blocks, float32
    d, ff, layers, h, kv, dh, v, dg = 64, 128, 2, 4, 2, 16, 256, 16
    per_layer = (2 * d * h * dh + 2 * d * kv * dh + 3 * d * ff + 2 * d
                 + h * dh * dg + 3 * kv * dh * dg + 2 * dh)
    weights = layers * per_layer + d + d * v
    assert wb.weight_params(conf) == weights
    kv_tok = layers * kv * dh * 4
    # 33 tokens: 5 visible blocks, 25 attended
    one = 2 * 25 * kv_tok + 5 * layers * kv * dg * 4 + 2 * kv_tok
    assert wb.step_bytes(conf, [33]) == weights * 4 + one
    assert wb.step_bytes(conf, [33, 33]) == weights * 4 + 2 * one
    untied = dict(conf, tie_word_embeddings=False)
    assert wb.step_bytes(untied, [33]) == weights * 4 + one + d * 4


def _run(step_ms, steps, conf):
    """A run whose step program reads ``step_ms`` a call, with decode
    steps of ``steps`` new lengths each."""
    sessions, k = {}, 0
    for j, lens in enumerate(steps):
        for n in lens:
            # a session of prompt n - 1 whose token 1 came from step j
            sessions[k] = types.SimpleNamespace(
                prompt_len=n - 1, times=[0.5, 1.0 + j], steps=[0, j])
            k += 1
    spec = types.SimpleNamespace(
        module=lambda kind, name: types.SimpleNamespace(
            reduce=lambda run: step_ms) if kind == "metrics"
        else SPEC.module(kind, name))
    return types.SimpleNamespace(
        spec=spec, conf=conf, sessions=sessions, t_open=0.0,
        t_close=100.0, t_stop=100.0, peaks={"hbm_bytes_per_s": 1e9},
        work=lambda name: SPEC.module("work", name))


def test_hbm_share_is_mean_step_bytes_over_step_time():
    conf = json.loads((tinycell.DATA / "tiny.json").read_text())
    wb = SPEC.module("work", "decode_step_bytes")
    share = SPEC.module("metrics", "decode_hbm_share").reduce
    steps = [[33, 40], [34]]
    mean = (wb.step_bytes(conf, [33, 40]) + wb.step_bytes(conf, [34])) / 2
    got = share(_run(2.0, steps, conf))
    assert got == pytest.approx(100.0 * mean / (2e-3 * 1e9))


def test_hbm_share_silent_without_a_step_program():
    conf = json.loads((tinycell.DATA / "tiny.json").read_text())
    share = SPEC.module("metrics", "decode_hbm_share").reduce
    assert share(_run(None, [[33]], conf)) is None
    assert share(_run(2.0, [], conf)) is None


@pytest.fixture
def scaled_root(tmp_path, monkeypatch):
    """The tiny cell, made a CPU-sized deepseek_coder_33b: 14/2 heads (a
    7:1 group), RoPE base 1e5 with linear x4, untied head, judged by the
    scaled reference."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    root = tinycell.make_root(tmp_path)
    path = root / "bench" / "configs" / "tiny.json"
    conf = json.loads(path.read_text())
    conf.update(program_base="deepseek_coder_33b",
                reference="dense_gate_lm_rope_linear",
                num_attention_heads=14, num_key_value_heads=2,
                tie_word_embeddings=False, rope_theta=100000.0,
                rope_scaling={"type": "linear", "factor": 4.0},
                max_position_embeddings=16384, qk_norm=False)
    path.write_text(json.dumps(conf))
    return root


def test_scaled_tiny_cell_is_correct_and_its_control_is_not(scaled_root):
    seed = 2 ** 31 + 99
    rc, prog, err = tinycell.run_tiny(scaled_root, seed)
    assert rc == 0 and prog["correct"] is True, err
    rc, ctrl, err = tinycell.run_tiny(scaled_root, seed, control=1)
    assert rc == 0 and ctrl["correct"] is False, err
