"""decode_hbm_share (model step): the bytes a paged decode step must move
(``work/decode_step_bytes.py``), averaged over the decode steps of the
traced run, over (the step program's device time per call, as
``decode_step_ms`` reads it, x the device's peak HBM bandwidth)."""
from harness.window import decode_steps


def reduce(run):
    step_ms = run.spec.module("metrics", "decode_step_ms").reduce(run)
    steps = list(decode_steps(run, run.t_stop).values())
    if not step_ms or not steps:
        return None
    step_bytes = run.work("decode_step_bytes").step_bytes
    mean = sum(step_bytes(run.conf, [s.prompt_len + i for s, i in toks])
               for toks in steps) / len(steps)
    return 100.0 * mean / (step_ms * 1e-3 * run.peaks["hbm_bytes_per_s"])
