"""Read a profiler trace and reduce it to device time.

``read(dir)`` turns the ``.xplane.pb`` that ``jax.profiler`` wrote into a
``Trace``: for each device plane, its events by line (name, start ns,
duration ns), and the host events, on the same clock. The reductions:

* ``busy_ns``        union of the intervals in which an operation ran on a
                     device (overlapping events count once);
* ``time_by``        device time and event count of the events whose name
                     a predicate accepts (a jitted program on the modules
                     line, a kernel on the ops line);
* ``top_ops``        the operations that took most device time;
* ``idle_gaps``      the longest gaps between busy intervals, each named
                     after the host event that covers most of it (the
                     benchmark's own ``bench.*`` spans first).

A ``Trace`` round-trips through JSON, so a small recorded trace can be
kept with the tests.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Callable, Dict, List, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_SPAN = "bench."


def profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-Python-call events
    opts.host_tracer_level = 2
    return opts


class Trace:
    """device: plane -> line -> (names, start_ns, dur_ns); host: the same
    for all host threads pooled under one line."""

    def __init__(self, device: Dict[str, Dict[str, tuple]],
                 host: tuple):
        self.device = device
        self.host = host

    # -- io ------------------------------------------------------------
    def to_json(self) -> str:
        def enc(t):
            return [list(t[0]), [int(x) for x in t[1]], [int(x) for x in t[2]]]
        return json.dumps({"device": {p: {ln: enc(t) for ln, t in lines.items()}
                                      for p, lines in self.device.items()},
                           "host": enc(self.host)})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)

        def dec(t):
            return (list(t[0]), np.asarray(t[1], np.int64),
                    np.asarray(t[2], np.int64))
        return cls({p: {ln: dec(t) for ln, t in lines.items()}
                    for p, lines in d["device"].items()}, dec(d["host"]))

    # -- reductions ----------------------------------------------------
    def planes(self) -> List[str]:
        return sorted(self.device)

    def ops(self, plane: str) -> tuple:
        lines = self.device[plane]
        if OPS_LINE in lines:
            return lines[OPS_LINE]
        names, st, du = [], [], []
        for t in lines.values():
            names += t[0]
            st.append(t[1])
            du.append(t[2])
        return names, np.concatenate(st), np.concatenate(du)

    def busy_intervals(self, plane: str) -> np.ndarray:
        """Merged [start, end) intervals in which the device was busy."""
        _, st, du = self.ops(plane)
        return merge(st, st + du)

    def busy_ns(self, plane: str) -> int:
        iv = self.busy_intervals(plane)
        return int((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0

    def time_by(self, plane: str, line: str,
                accept: Callable[[str], bool]) -> Tuple[int, int]:
        """(device ns, event count) of the events of ``line`` whose name
        ``accept`` takes, overlaps within one line counted once."""
        if line not in self.device[plane]:
            return 0, 0
        names, st, du = self.device[plane][line]
        pick = np.fromiter((accept(n) for n in names), bool, len(names))
        if not pick.any():
            return 0, 0
        iv = merge(st[pick], (st + du)[pick])
        return int((iv[:, 1] - iv[:, 0]).sum()), int(pick.sum())

    def modules_holding(self, plane: str,
                        accept: Callable[[str], bool]) -> np.ndarray:
        """Mask over the program line's events: which programs ran at
        least one op that ``accept`` takes (programs are told apart by
        what they run where their names are not unique)."""
        names, st, du = self.device[plane].get(MODULES_LINE, ([], [], []))
        if not names or OPS_LINE not in self.device[plane]:
            return np.zeros(len(names), bool)
        on, ost, _ = self.device[plane][OPS_LINE]
        pick = np.fromiter((accept(n) for n in on), bool, len(on))
        hits = np.sort(ost[pick])
        lo = np.searchsorted(hits, st, side="left")
        hi = np.searchsorted(hits, st + du, side="left")
        return hi > lo

    def module_time(self, plane: str, mask: np.ndarray) -> Tuple[int, int]:
        """(device ns, count) of the program events under ``mask``."""
        _, st, du = self.device[plane][MODULES_LINE]
        if not mask.any():
            return 0, 0
        iv = merge(st[mask], (st + du)[mask])
        return int((iv[:, 1] - iv[:, 0]).sum()), int(mask.sum())

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Operations by device seconds, averaged over the planes."""
        tot: Dict[str, float] = {}
        for p in self.planes():
            names, _, du = self.ops(p)
            for name, d in zip(names, du):
                tot[name] = tot.get(name, 0.0) + float(d)
        k = max(len(self.planes()), 1)
        return [(name, t / k * 1e-9) for name, t in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, plane: str, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` longest gaps between busy intervals, each named after
        the host event that overlaps it most (``bench.*`` spans preferred,
        then the shortest such event); ``host`` where none does."""
        iv = self.busy_intervals(plane)
        if len(iv) < 2:
            return []
        g0, g1 = iv[:-1, 1], iv[1:, 0]
        order = np.argsort(-(g1 - g0))[:n]
        hn, hs, hd = self.host
        he = hs + hd
        out = []
        for i in order:
            a, b = int(g0[i]), int(g1[i])
            ov = np.minimum(he, b) - np.maximum(hs, a)
            cand = np.nonzero(ov > 0)[0]
            label = "host"
            if cand.size:
                best = max(cand, key=lambda j: (hn[j].startswith(BENCH_SPAN),
                                                ov[j], -hd[j]))
                label = hn[best]
            out.append((label, (b - a) * 1e-9))
        return out


def merge(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals as sorted, disjoint rows."""
    if len(starts) == 0:
        return np.zeros((0, 2), np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.nonzero(new)[0]
    ends_out = np.append(run_end[idx[1:] - 1], run_end[-1])
    return np.stack([s[idx], ends_out], axis=1)


def short_name(name: str) -> str:
    """An XLA op event is named by its whole HLO instruction
    (``%copy.151 = bf16[...] copy(...)``): keep the part before ``=``."""
    return name.split(" = ", 1)[0]


def read(log_dir: str) -> Trace:
    """The newest ``.xplane.pb`` under ``log_dir`` as a ``Trace``."""
    from jax._src.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    device: Dict[str, Dict[str, tuple]] = {}
    hn: List[str] = []
    hs: List[int] = []
    hd: List[int] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {}
            for line in plane.lines:
                names, st, du = [], [], []
                for ev in line.events:
                    names.append(short_name(ev.name))
                    st.append(ev.start_ns)
                    du.append(ev.duration_ns)
                if names:
                    lines[line.name] = (names, np.asarray(st, np.int64),
                                        np.asarray(du, np.int64))
            if lines:
                device[plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    hn.append(ev.name)
                    hs.append(ev.start_ns)
                    hd.append(ev.duration_ns)
    return Trace(device, (hn, np.asarray(hs, np.int64),
                          np.asarray(hd, np.int64)))
