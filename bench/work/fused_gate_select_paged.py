"""fused_gate_select_paged, one call (one layer, every active slot): the
Kg row of every visible block and the slot's gate query in, the selected
block ids out; FLOPs are the block scores."""
from harness.work_common import dims, visible_blocks


def work(conf, new_lens):
    """(flops, bytes) of one call over slots with these lengths."""
    m = dims(conf)
    flops = nbytes = 0
    for n in new_lens:
        nv = visible_blocks(n, m["ps"])
        flops += 2 * m["hkv"] * m["dg"] * nv
        nbytes += ((nv + 1) * m["hkv"] * m["dg"] * m["itemsize"]
                   + m["hkv"] * m["k"] * 4)
    return flops, nbytes
