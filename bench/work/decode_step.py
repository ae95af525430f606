"""Model FLOPs of one decoded token: 2 x every matmul parameter (the head
and the gate's query projection included), plus 4 x layers x query heads
x head_dim x attended tokens, plus the gate's block scores. Recomputed or
copied work does not count."""
from harness.work_common import attended, dims, visible_blocks


def matmul_params(conf):
    m = dims(conf)
    per_layer = (2 * m["d"] * m["h"] * m["dh"]           # wq, wo
                 + 2 * m["d"] * m["hkv"] * m["dh"]       # wk, wv
                 + 3 * m["d"] * m["ff"]                  # gate, up, down
                 + m["h"] * m["dh"] * m["dg"])           # gate query proj
    return m["layers"] * per_layer + m["d"] * m["vocab"]


def flops_per_token(conf, new_len):
    m = dims(conf)
    att = attended(new_len, m["ps"], m["k"])
    scores = 2 * m["hkv"] * m["dg"] * visible_blocks(new_len, m["ps"])
    return (2 * matmul_params(conf)
            + m["layers"] * (4 * m["h"] * m["dh"] * att + scores))
