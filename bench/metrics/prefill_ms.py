"""prefill_ms (prefill: bucketed ``lm_prefill`` + ``scatter_prefill``):
device time of those programs in the trace per admission in the traced
interval. ``lm_prefill`` is jitted from a ``functools.partial`` and has no
name of its own in the trace: it is the program with a layer loop
(``%while``) that runs no decode kernel."""
import numpy as np

from harness.trace import MODULES_LINE

DECODE = "%block_sparse_decode_paged"


def reduce(run):
    if run.trace is None:
        return None
    admitted = sum(1 for s in run.sessions.values()
                   if s.times and run.t_open <= s.times[0] <= run.t_stop)
    planes = run.trace.planes()
    ns = 0
    for p in planes:
        t = run.trace
        names = t.device[p].get(MODULES_LINE, ([],))[0]
        loop = t.modules_holding(p, lambda n: n.startswith("%while"))
        decode = t.modules_holding(p, lambda n: n.startswith(DECODE))
        scatter = np.fromiter(("scatter_prefill" in n for n in names), bool,
                              len(names))
        ns += t.module_time(p, (loop & ~decode) | scatter)[0]
    ns /= max(len(planes), 1)
    return ns * 1e-6 / admitted if admitted and ns else None
