"""decode_mfu (the whole decode step): the model FLOPs of every token
decoded in the window over (window x chips x peak bf16 FLOP/s of the
device), FLOPs per token from ``work/decode_step.py``."""
from harness.window import decode_steps


def reduce(run):
    work = run.work("decode_step")
    flops = sum(work.flops_per_token(run.conf, s.prompt_len + i)
                for toks in decode_steps(run).values() for s, i in toks)
    if not flops:
        return None
    return 100.0 * flops / ((run.t_close - run.t_open) * run.chips
                            * run.peaks["bf16_flops"])
