"""gate_select_roofline (kernel ``fused_gate_select_paged``): least time
of its calls (the Kg rows of every visible block and the gate query at
peak HBM bandwidth) over its device time in the trace."""
from harness.roofline import share

KERNEL = "%fused_gate_select_paged"


def reduce(run):
    return share(run, "fused_gate_select_paged", lambda n: n.startswith(KERNEL))
