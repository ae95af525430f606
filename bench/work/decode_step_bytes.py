"""Bytes one paged decode step must move in HBM, over its active slots:
every weight once, at its served dtype (the untied embedding table only
by the rows the step looks up); per active slot the K and V of the tokens
it attends to, the Kg rows of its visible blocks and the K and V of the
token it appends, in every layer. Recomputed or copied bytes do not
count."""
from harness.work_common import attended, dims, visible_blocks


def weight_params(conf):
    """Parameters read whole by every step: the layers (attention, qk-norm
    scales, MLP, both RMSNorm scales, the gate's query and key
    projections), the final norm and the head."""
    m = dims(conf)
    per_layer = (2 * m["d"] * m["h"] * m["dh"]           # wq, wo
                 + 2 * m["d"] * m["hkv"] * m["dh"]       # wk, wv
                 + 3 * m["d"] * m["ff"]                  # gate, up, down
                 + 2 * m["d"]                            # ln1, ln2
                 + m["h"] * m["dh"] * m["dg"]            # gate query proj
                 + 3 * m["hkv"] * m["dh"] * m["dg"])     # gate key proj
    if conf["qk_norm"]:
        per_layer += 2 * m["dh"]
    return m["layers"] * per_layer + m["d"] + m["d"] * m["vocab"]


def step_bytes(conf, new_lens):
    """Bytes of one step over active slots whose lengths after the append
    are ``new_lens``."""
    m = dims(conf)
    kv_token = m["layers"] * m["hkv"] * m["dh"] * m["itemsize"]
    kg_block = m["layers"] * m["hkv"] * m["dg"] * m["itemsize"]
    nbytes = weight_params(conf) * m["itemsize"]
    for n in new_lens:
        if not conf["tie_word_embeddings"]:
            nbytes += m["d"] * m["itemsize"]             # the token's row
        nbytes += (2 * attended(n, m["ps"], m["k"]) * kv_token
                   + visible_blocks(n, m["ps"]) * kg_block
                   + 2 * kv_token)
    return nbytes
