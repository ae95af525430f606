"""A checkout-shaped temporary root holding a CPU-sized cell."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_CELL = "tiny.tiny_mix"


def make_root(tmp: Path, *, gap_limit: float = 0.005) -> Path:
    """Copy ``bench/`` and the program into ``tmp`` and add the tiny
    configuration, mix and limits as new files, with a BENCHMARK.json
    that names them. The tiny cell runs in float32, where the program's
    mean gap reads 0 and the float8 control's 0.017-0.068 (six seeds), so
    ``gap_limit`` lies between them."""
    root = Path(tmp)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    shutil.copy(DATA / "tiny.json", root / "bench" / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny_mix.json",
                root / "bench" / "mixes" / "tiny_mix.json")
    (root / "bench" / "limits" / f"{TINY_CELL}.json").write_text(
        json.dumps({"served_gap_mean": gap_limit}))
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "tiny", "source": "test",
                           "file": "bench/configs/tiny.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": TINY_CELL, "config": "tiny",
                             "traffic": "tiny_mix", "chips": 1,
                             "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def load_run():
    """bench/run.py as a module of its own name."""
    import importlib.util
    name = "bench_run_entry"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, BENCH / "run.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def run_tiny(root: Path, seed: int, *, seconds: float = 2.0, trace: int = 0,
             control: int = 0):
    """One run of the tiny cell on the CPU, past the look for a chip.
    Returns (exit code, result line as a dict or None, stderr text)."""
    import io
    import time
    out, err = io.StringIO(), io.StringIO()
    rc = load_run().main(
        ["--workload", TINY_CELL, "--seed", str(seed), "--seconds",
         str(seconds), "--trace", str(trace), "--control", str(control)],
        root=root, require_tpu=False,
        t_start=time.perf_counter(), out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
