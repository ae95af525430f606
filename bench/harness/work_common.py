"""Sizes shared by the work counts, read from a configuration file."""


def dims(conf):
    h = conf["num_attention_heads"]
    return {"d": conf["hidden_size"], "ff": conf["intermediate_size"],
            "layers": conf["num_hidden_layers"], "h": h,
            "hkv": conf["num_key_value_heads"],
            "dh": conf.get("head_dim") or conf["hidden_size"] // h,
            "vocab": conf["vocab_size"], "ps": conf["gate"]["block_size"],
            "dg": conf["gate"]["d_gate"],
            "k": max(-(-conf["budget_tokens"] // conf["gate"]["block_size"]),
                     2),
            "itemsize": {"bfloat16": 2, "float16": 2,
                         "float32": 4}[conf["torch_dtype"]]}


def visible_blocks(new_len, ps):
    return -(-new_len // ps)


def attended(new_len, ps, k):
    """Tokens one decode row attends to: every visible token while k or
    fewer blocks are visible, else k - 1 whole blocks and the valid part
    of the last one."""
    nv = visible_blocks(new_len, ps)
    if nv <= k:
        return new_len
    return (k - 1) * ps + new_len - (nv - 1) * ps
