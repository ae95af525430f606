"""Window arithmetic over a run's token records.

A run's window is [t_open, t_close] on the host clock. A token is in the
window when the host had it inside that interval; an inter-token gap
counts when both of its tokens are in the window; a request is due in the
window when its due time (closed loop: the end of its client's previous
request) is inside it. Tails are taken over all samples, pooled across
requests, by linear interpolation between order statistics.
"""
from __future__ import annotations

from typing import List, Sequence


def quantile(values: Sequence[float], q: float) -> float:
    """The q-quantile (0 <= q <= 1), linear between order statistics."""
    xs = sorted(values)
    if not xs:
        raise ValueError("quantile of no samples")
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(t: float, run) -> bool:
    return run.t_open <= t <= run.t_close


def window_tokens(run) -> List[tuple]:
    """(session, index, time) of every token the host had in the window."""
    return [(s, i, t) for s in run.sessions.values()
            for i, t in enumerate(s.times) if in_window(t, run)]


def gaps(run) -> List[float]:
    """Every inter-token gap (seconds) whose two tokens are in the window."""
    out = []
    for s in run.sessions.values():
        ts = s.times
        out += [b - a for a, b in zip(ts, ts[1:])
                if in_window(a, run) and in_window(b, run)]
    return out


def ttfts(run) -> List[float]:
    """Time to first token (seconds) of every request due in the window,
    from its due time; a request without a first token by the close
    counts with the time it has waited."""
    out = []
    for s in run.sessions.values():
        if s.t_due is None or not in_window(s.t_due, run):
            continue
        first = s.times[0] if s.times else None
        out.append((first if first is not None and first <= run.t_close
                    else run.t_close) - s.t_due)
    return out


def decode_steps(run, end: float = None) -> dict:
    """Virtual-clock step -> [(session, index)] of the decode tokens the
    host had between the window's open and ``end`` (default: the close).
    A session's token 0 comes from its prefill, every later token from
    one decode step. With ``end=run.t_stop`` (when ``serve`` was left)
    these are the steps a trace taken over the run holds."""
    end = run.t_close if end is None else end
    steps: dict = {}
    for s in run.sessions.values():
        for i in range(1, len(s.times)):
            if run.t_open <= s.times[i] <= end:
                steps.setdefault(s.steps[i], []).append((s, i))
    return steps

