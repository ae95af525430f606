"""slot_occupancy (scheduler): decode tokens in the window over (decode
steps in the window x slots), steps taken from the engine's virtual
clock as ``on_token`` reports it."""
from harness.window import decode_steps


def reduce(run):
    steps = decode_steps(run)
    if not steps:
        return None
    toks = sum(len(v) for v in steps.values())
    return 100.0 * toks / (len(steps) * run.n_slots)
