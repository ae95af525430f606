"""sparse_attn_roofline (kernel ``block_sparse_decode_paged``): least time
of its calls (bytes of the selected K/V, q and out at peak HBM bandwidth,
or its FLOPs at peak) over its device time in the trace."""
from harness.roofline import share

KERNEL = "%block_sparse_decode_paged"


def reduce(run):
    return share(run, "block_sparse_decode_paged",
                 lambda n: n.startswith(KERNEL))
