"""decode_step_ms (model step): device time of the jitted paged decode
step per call, from the trace's program line. The step is jitted from a
``functools.partial`` and so carries no name of its own there
(``jit__unknown``): it is the program that runs the paged block-sparse
decode kernel."""
KERNEL = "%block_sparse_decode_paged"


def reduce(run):
    if run.trace is None:
        return None
    ns = calls = 0
    for p in run.trace.planes():
        t, c = run.trace.module_time(p, run.trace.modules_holding(
            p, lambda n: n.startswith(KERNEL)))
        ns, calls = ns + t, calls + c
    return ns / calls * 1e-6 if calls else None
