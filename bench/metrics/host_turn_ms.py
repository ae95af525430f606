"""host_turn_ms (scheduler): host time per decode step not spent waiting
on the device: over the program's ``serve.step`` spans that hold a
``serve.dispatch``, the mean of (span duration - union of the
``serve.sync`` spans inside it). Read from the program's own host spans
(``harness/spans.py``); a program without them reads nothing."""
from harness import spans


def reduce(run):
    if run.trace is None:
        return None
    return spans.host_turn_ms(run.trace)
