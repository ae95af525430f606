"""The serving program's own host spans in a ``Trace``: its decode steps
and the blocking device-to-host pulls inside them.

``DecodeEngine.serve`` wraps each loop iteration in a ``serve.step`` span,
the step program's dispatch in ``serve.dispatch``, and every blocking pull
in a ``serve.sync`` span of its own (see the README, "Tracing a served
run"). The profiler records a span only if it began and ended while the
trace was on, so every step span in a trace lies wholly inside it; the
children of a step cut by the trace's start or stop are left with no step
around them and are not counted.

* ``decode_steps``  the step spans that hold a ``serve.dispatch``: the
                    iterations that ran the step program (an iteration
                    that only pulled arrivals or waited runs none);
* ``per_step``      for each of those steps, its duration and the number
                    and union of the ``serve.sync`` spans inside it.

A trace with no ``serve.*`` spans (a program without them) gives no steps,
and each reader returns ``None``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from harness.trace import merge

STEP = "serve.step"
DISPATCH = "serve.dispatch"
SYNC = "serve.sync"


def _spans(trace, name: str) -> np.ndarray:
    """[start, end) ns rows of the host spans called ``name``, by start."""
    names, st, du = trace.host
    pick = np.fromiter((n == name for n in names), bool, len(names))
    iv = np.stack([st[pick], st[pick] + du[pick]], axis=1).astype(np.int64)
    return iv[np.argsort(iv[:, 0], kind="stable")]


def _owner(steps: np.ndarray, iv: np.ndarray) -> np.ndarray:
    """For each row of ``iv``, the index of the step span (disjoint rows,
    by start) that holds it wholly, or -1."""
    i = np.searchsorted(steps[:, 0], iv[:, 0], side="right") - 1
    ok = (i >= 0) & (iv[:, 1] <= steps[np.maximum(i, 0), 1])
    return np.where(ok, i, -1)


def decode_steps(trace) -> np.ndarray:
    """[start, end) ns rows of the step spans that hold a dispatch."""
    steps = _spans(trace, STEP)
    if not len(steps):
        return steps
    held = _owner(steps, _spans(trace, DISPATCH))
    return steps[np.unique(held[held >= 0])]


def per_step(trace) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(duration ns, sync count, sync union ns) of each decode step."""
    steps = decode_steps(trace)
    n = len(steps)
    count = np.zeros(n, np.int64)
    waited = np.zeros(n, np.int64)
    if n:
        syncs = _spans(trace, SYNC)
        own = _owner(steps, syncs)
        for i in range(n):
            iv = merge(*syncs[own == i].T)
            count[i] = int((own == i).sum())
            waited[i] = int((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0
    return steps[:, 1] - steps[:, 0], count, waited


def host_turn_ms(trace) -> Optional[float]:
    """Mean over decode steps of (step span - union of its syncs), ms."""
    dur, _, waited = per_step(trace)
    return float(np.mean(dur - waited)) * 1e-6 if len(dur) else None


def syncs_per_step(trace) -> Optional[float]:
    """``serve.sync`` spans inside decode steps over the number of steps."""
    _, count, _ = per_step(trace)
    return float(count.sum()) / len(count) if len(count) else None
