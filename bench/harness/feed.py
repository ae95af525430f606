"""Drive ``DecodeEngine.serve`` on wall time through its public seams.

``serve(arrivals=..., on_token=...)`` is fed by a ``Feed``:

* ``pull(step)`` hands the engine every request that is due on the wall
  clock (closed loop: when its client's previous request ended), and
  raises ``WindowClosed`` once the measured window is over, so a run ends
  without draining sessions that would take minutes to finish;
* ``on_token(req, token, index, step)`` stamps every token with the wall
  time at which the host has it, and opens the window once every initial
  session has its first token.

Host spans named ``bench.pull`` and ``bench.on_token`` go into the
profiler's trace (when one is taken), so idle gaps on the device can be
attributed to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional


class WindowClosed(Exception):
    """Raised through ``serve()`` when the measured window ends."""


@dataclasses.dataclass
class Session:
    rid: int
    client: int
    prompt: Any                     # int32 token ids
    max_new: int
    greedy: bool
    initial: bool
    t_due: Optional[float] = None   # wall time the request became due
    t_handed: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    steps: List[int] = dataclasses.field(default_factory=list)
    req: Any = None                 # the engine's Request, once seen

    @property
    def prompt_len(self) -> int:
        return int(len(self.prompt))


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Feed:
    """The ``arrivals=`` object and the ``on_token=`` callback of one run."""

    exhausted = False

    def __init__(self, traffic, seconds: float, *,
                 on_open: Optional[Callable[[], None]] = None,
                 clock: Callable[[], float] = time.perf_counter):
        self.traffic = traffic
        self.seconds = float(seconds)
        self.on_open = on_open
        self.clock = clock
        self.sessions: Dict[int, Session] = {}
        self.live: set = set()                # rids not yet ended
        self.n_initial = 0
        self.n_first = 0
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None

    def _session(self, rd: Dict[str, Any], initial: bool) -> Dict[str, Any]:
        s = Session(rid=rd["rid"], client=rd["client"], prompt=rd["tokens"],
                    max_new=rd["max_new_tokens"], greedy=rd["greedy"],
                    initial=initial, t_due=rd.get("due"))
        self.sessions[s.rid] = s
        self.live.add(s.rid)
        out = {"rid": s.rid, "tokens": rd["tokens"],
               "max_new_tokens": s.max_new}
        if rd.get("sampling"):
            from repro.serve.sampling import SamplingParams
            out["sampling"] = SamplingParams(**rd["sampling"])
        return out

    def initial_requests(self) -> List[Dict[str, Any]]:
        reqs = [self._session(rd, True) for rd in self.traffic.initial()]
        self.n_initial = len(reqs)
        return reqs

    def pull(self, step: int) -> List[Dict[str, Any]]:
        with _span("bench.pull"):
            now = self.clock()
            if self.t_close is not None and now >= self.t_close:
                raise WindowClosed()
            for rid in list(self.live):
                # a request the engine failed ends its client's turn too
                s = self.sessions[rid]
                if s.req is not None and s.req.status != "ok":
                    self._end(s, now)
            out = []
            for rd in self.traffic.due(now):
                out.append(self._session(rd, False))
                self.sessions[rd["rid"]].t_handed = now
            return out

    def on_token(self, req, token: int, index: int, step: int) -> None:
        with _span("bench.on_token"):
            now = self.clock()
            s = self.sessions[req.rid]
            s.req = req
            s.tokens.append(int(token))
            s.times.append(now)
            s.steps.append(int(step))
            if index == 0 and s.initial:
                self.n_first += 1
                if self.n_first == self.n_initial:
                    self._open()
            if index == s.max_new - 1:
                self._end(s, now)

    def _end(self, s: Session, now: float) -> None:
        self.live.discard(s.rid)
        self.traffic.finished(s.client, now)

    def _open(self) -> None:
        if self.on_open is not None:
            self.on_open()
        self.t_open = self.clock()
        self.t_close = self.t_open + self.seconds


class NoArrivals:
    """An arrivals object with nothing to send: lets the warm-up compile
    the same decode-step program as the window (``serve`` passes per-slot
    budget caps whenever ``arrivals`` is given)."""

    exhausted = True

    def pull(self, step: int) -> List[Dict[str, Any]]:
        return []
