"""setup_s: process start to window open: loading, weights, warm-up and
compilation, and the prefill of the initial sessions."""


def reduce(run):
    return run.t_open - run.t_start
