"""A kernel's share of its roofline over a traced run.

The least time the chip could take for one call is the larger of its
operations over the peak FLOP/s and its bytes over the peak HBM bandwidth
(``bench/work/<kernel>.py`` counts both from the shapes the call had).
The share is the sum of those least times over every call in the traced
steps, over the kernel's device time in the trace."""
from __future__ import annotations

from harness.trace import OPS_LINE
from harness.window import decode_steps


def share(run, work_name: str, accept) -> float:
    if run.trace is None:
        return None
    layers = run.conf["num_hidden_layers"]
    work = run.work(work_name).work
    least = 0.0
    for toks in decode_steps(run, run.t_stop).values():
        f, b = work(run.conf, [s.prompt_len + i for s, i in toks])
        least += layers * max(f / run.peaks["bf16_flops"],
                              b / run.peaks["hbm_bytes_per_s"])
    ns = [run.trace.time_by(p, OPS_LINE, accept)[0]
          for p in run.trace.planes()]
    busy = sum(ns) / max(len(ns), 1) * 1e-9
    if not busy or not least:
        return None
    return 100.0 * least / busy
