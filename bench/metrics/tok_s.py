"""tok_s: every token the host had in the window, over the window's seconds."""
from harness.window import window_tokens


def reduce(run):
    return len(window_tokens(run)) / (run.t_close - run.t_open)
