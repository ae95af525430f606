"""End-to-end runs of a CPU-sized cell through bench/run.py, past the look
for a chip: a cell, configuration, mix and metric added as new files are
found and run, and the float8 control reads far above the program."""
from __future__ import annotations

import json

import numpy as np
import pytest

import tinycell


@pytest.fixture
def root(tmp_path, monkeypatch):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    return tinycell.make_root(tmp_path)


def test_new_files_are_found_and_run(root):
    """The tiny configuration, mix, limits and an extra metric live only in
    new files; BENCHMARK.json names them and nothing else changes."""
    (root / "bench" / "metrics" / "tiny_tokens.py").write_text(
        "from harness.window import window_tokens\n\n\n"
        "def reduce(run):\n    return len(window_tokens(run))\n")
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["end_to_end"].append({"name": "tiny_tokens", "unit": "tokens",
                              "better": "higher", "bound": 0.05,
                              "source": "host_clock",
                              "workloads": [tinycell.TINY_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    before = {p: p.read_bytes() for p in (tinycell.BENCH).rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    rc, res, err = tinycell.run_tiny(root, 2 ** 31 + 12345)
    assert rc == 0, err
    assert res["correct"] is True, err
    assert res["failed"] == 0 and res["attempted"] >= 2
    m = res["metrics"]
    assert set(m) == {"tok_s", "itl_p95_ms", "setup_s", "tiny_tokens"}
    assert m["tiny_tokens"]["value"] > 0
    assert list(res)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check: served_gap_mean")
    after = {p: p.read_bytes() for p in (tinycell.BENCH).rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert before == after


def test_no_result_without_the_program(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ gives no result."""
    import os
    import subprocess
    import sys
    root = tinycell.make_root(tmp_path)
    (root / "src").unlink()
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", tinycell.TINY_CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=root,
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_control_reads_above_the_program(root):
    """The float8 reference in the program's place, judged by the same
    limits through the same run, comes out not correct on the seeds where
    the program comes out correct."""
    for seed in (77, 2 ** 31 + 5):
        rc, prog, err = tinycell.run_tiny(root, seed)
        assert rc == 0 and prog["correct"] is True, err
        rc, ctrl, err = tinycell.run_tiny(root, seed, control=1)
        assert rc == 0, err
        assert ctrl["correct"] is False, err
        assert "compared (control" in err
        p = prog["checks"]["served_gap_mean"]
        c = ctrl["checks"]["served_gap_mean"]
        assert np.isfinite(c["value"]) and c["value"] > c["limit"] > p["value"]
