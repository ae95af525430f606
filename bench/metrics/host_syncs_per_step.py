"""host_syncs_per_step (scheduler): blocking device-to-host pulls per
decode step: ``serve.sync`` spans inside the program's ``serve.step``
spans that hold a ``serve.dispatch``, over the number of those steps
(``harness/spans.py``); a program without the spans reads nothing."""
from harness import spans


def reduce(run):
    if run.trace is None:
        return None
    return spans.syncs_per_step(run.trace)
