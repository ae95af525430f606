"""Decide ``correct``: what the window served, against the plain reference.

After the window has closed and the program's state is freed, a sample of
the greedy sessions, drawn from the seed and always holding the session
with the most served tokens, is recomputed by the configuration's
reference (``bench/reference/<name>.py``) over its prompt and the tokens
it was served, teacher-forced. At every served token the reference's best
logit minus its logit of the served token is read. Their widest
(``served_gap_max``) and their mean over every compared row
(``served_gap_mean``) are the numbers a cell's limits file
(``bench/limits/<cell>.json``) can hold to a limit.
Served tokens come from the engine's bucketed prefill and page scatter
(the first) and from its paged decode step with gate selection at the
cell's budget (every later one), so the comparison covers both, over every
page boundary the sessions crossed.

The control is the same reference in float8 (e4m3) in the program's place:
at the same positions the token it ranks first is read against the
float32 reference the same way.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def pick_sessions(sessions, n: int, rng) -> List[Any]:
    """Up to ``n`` greedy sessions with served tokens: the one with the
    most, and the rest drawn from ``rng``."""
    cand = sorted((s for s in sessions if s.greedy and s.tokens),
                  key=lambda s: (-len(s.tokens), s.rid))
    if not cand:
        return []
    rest = cand[1:]
    take = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [cand[0]] + [rest[i] for i in sorted(take)]


def padded_len(mix: Dict[str, Any], block: int) -> int:
    """The reference's sequence length: the longest session of the mix,
    rounded up to 1024 tokens (a whole number of blocks and of query
    chunks), so every session shares one compiled program."""
    longest = mix["prompt_tokens"][1] + mix["new_tokens"][1]
    unit = max(1024, block)
    return -(-longest // unit) * unit


def session_gaps(ref, params, conf, mix, s, precision: str):
    """Per served token of session ``s``: the f32 reference's best logit
    minus its logit of the token the program served (``precision="f32"``)
    or of the token the reference at ``precision`` ranks first."""
    n = len(s.tokens)
    t_pad = padded_len(mix, conf["gate"]["block_size"])
    seq = np.zeros((t_pad,), np.int32)
    seq[:s.prompt_len] = s.prompt
    seq[s.prompt_len:s.prompt_len + n - 1] = s.tokens[:-1]
    n_out = mix["new_tokens"][1]
    served = np.asarray(s.tokens, np.int32)
    h32 = ref.final_hidden(params, conf, seq, s.prompt_len, n_out)[:n]
    if precision == "f32":
        best, at, _ = ref.logit_stats(params, h32, served)
    else:
        hlo = ref.final_hidden(params, conf, seq, s.prompt_len, n_out,
                               precision=precision)[:n]
        _, _, picks = ref.logit_stats(params, hlo, served, precision)
        best, at, _ = ref.logit_stats(params, h32, picks)
    return best - at


def compare(ref, params, conf, mix, sessions, rng,
            precision: str = "f32") -> Dict[str, float]:
    """{"gap_mean", "gap_max", "agree", "rows", "sessions"} over the
    sample: the mean and the widest gap over every compared row, and the
    share of rows whose token is the reference's first. ``precision``
    "fp8" puts the control in the program's place."""
    picked = pick_sessions(sessions, int(mix["check_sessions"]), rng)
    gaps = [session_gaps(ref, params, conf, mix, s, precision)
            for s in picked]
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    nan = float("nan")
    return {"gap_mean": float(g.mean()) if g.size else nan,
            "gap_max": float(g.max()) if g.size else nan,
            "agree": float((g <= 0).mean()) if g.size else nan,
            "rows": int(g.size), "sessions": len(picked)}
