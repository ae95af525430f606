"""block_sparse_decode_paged, one call (one layer, every active slot):
the K and V of the tokens each slot attends to, its queries and its
output; FLOPs are q.k and p.v over those tokens."""
from harness.work_common import attended, dims


def work(conf, new_lens):
    """(flops, bytes) of one call over slots with these lengths."""
    m = dims(conf)
    flops = nbytes = 0
    for n in new_lens:
        att = attended(n, m["ps"], m["k"])
        flops += 4 * m["h"] * m["dh"] * att
        nbytes += (2 * att * m["hkv"] * m["dh"]
                   + 2 * m["h"] * m["dh"]) * m["itemsize"]
    return flops, nbytes
