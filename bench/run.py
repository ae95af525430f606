#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell (``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<mix>.json``). The run

1. makes the weights from the seed on the device, builds the engine with
   the configuration's default decode options and budget, warms up every
   program the traffic will use, and admits and prefills the initial
   sessions: that is set-up (``setup_s``);
2. serves the traffic through ``DecodeEngine.serve`` for ``--seconds``,
   stamping every token on the host, and leaves ``serve`` when the window
   closes (``--trace 1``: with the profiler on);
3. reads the peak device memory, frees the engine's state, and checks a
   sample of what the window served against the configuration's plain
   reference (``correct``);
4. prints the numbers compared, each beside its limit, as the last lines
   of standard error, and one JSON object as the last line of standard
   output: the cell's end-to-end metrics (``--trace 0``) or its per-layer
   metrics (``--trace 1``).

``--control 1`` puts the float8 reference in the program's place in
step 3, so the same limits judge the control (what a cell's limits in
``bench/limits/<cell>.json`` are set against; it should read
``correct: false``). The benchmark's own runs leave it at 0.

Without a TPU, with fewer chips than the cell asks for, or outside a
checkout, it exits non-zero and prints no result. Compiled programs are
kept in JAX's persistent cache (``JAX_COMPILATION_CACHE_DIR`` if set, else
``<checkout>/.jax_cache``); traces go to ``<checkout>/bench_out/``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness.spec import Spec, SpecError  # noqa: E402


class NoDevice(Exception):
    pass


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoDevice(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"cell needs {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def peaks_for(kind: str):
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoDevice(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True,
         t_start: float = T_START, out=sys.stdout, err=sys.stderr) -> int:
    args = parse(argv)
    try:
        spec = Spec(root)
        cell = spec.cell(args.workload)
        conf = spec.config(cell["config"])
        mix = spec.mix(cell["traffic"])
        limits = spec.limits(cell["name"])
    except SpecError as e:
        print(f"bench: {e}", file=err)
        return 2
    sys.path.insert(0, str(root / "src"))
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"bench: the system under test is missing ({e})", file=err)
        return 2
    enable_compile_cache()
    try:
        devs = device_info(int(cell["chips"]), require_tpu)
        peaks = peaks_for(devs[0].device_kind) if require_tpu else \
            {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
    except NoDevice as e:
        print(f"bench: {e}", file=err)
        return 1
    from harness.cell import run_cell
    result, lines = run_cell(spec, cell, conf, mix, limits, devs, peaks,
                             seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), t_start=t_start,
                             out_dir=root / "bench_out",
                             control=bool(args.control))
    for line in lines:
        print(line, file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
