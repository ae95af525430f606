"""One run of one cell: set-up, the measured window, metrics, the check."""
from __future__ import annotations

import dataclasses
import math
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

from harness import check as chk
from harness import window as win
from harness.feed import Feed, NoArrivals, WindowClosed
from harness.model import decode_options, make_params, program_config


@dataclasses.dataclass
class Run:
    """What a metric reducer reads (``bench/metrics/<name>.py``)."""
    spec: Any
    conf: Dict[str, Any]
    mix: Dict[str, Any]
    sessions: Dict[int, Any]
    t_start: float
    t_open: float
    t_close: float
    t_stop: float
    n_slots: int
    chips: int
    peaks: Dict[str, float]
    trace: Any = None               # harness.trace.Trace, traced runs only
    trace_window_s: Optional[float] = None

    def work(self, name: str):
        return self.spec.module("work", name)


class CompileCounter:
    """Counts JAX compile events between ``arm()`` and ``disarm()``."""

    def __init__(self):
        import jax
        self.armed = False
        self.events: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and "compile" in event:
            self.events.append(event)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def warm_up(eng, traffic, serve_kw: Dict[str, Any], ps: int) -> None:
    """Compile every program the window will run, on the engine it uses:
    one request per prefill bucket the traffic can send (its longest
    prompt, so the page-id padding of the scatter is covered too), one
    sampled request where the traffic samples, decode steps with the same
    slots, pool and page table, and the page-row sweeps a retirement can
    ask for (every power of two up to the pool)."""
    import jax
    from repro.serve import paging as pg
    from repro.serve.sampling import SamplingParams
    longest: Dict[int, int] = {}
    for n in traffic.prompt_lengths():
        b = _pow2(-(-n // ps))
        longest[b] = max(longest.get(b, 0), n)
    reqs = [{"rid": i, "max_new_tokens": 3,
             "tokens": np.zeros((n,), np.int32)}
            for i, n in enumerate(sorted(longest.values()))]
    sampled = traffic.sampled()
    if sampled:
        reqs.append({"rid": len(reqs), "max_new_tokens": 3,
                     "tokens": np.zeros((min(longest.values()),), np.int32),
                     "sampling": SamplingParams(**sampled)})
    eng.serve(reqs, arrivals=NoArrivals(), **serve_kw)
    opts = eng.options
    pages = pg.init_pages(eng.cfg, serve_kw["num_pages"],
                          eng.api.paged_attn_layers(eng.cfg),
                          with_meta=opts.policy.needs_meta,
                          quantize=opts.quantize)
    top = serve_kw["num_pages"] - 1
    n = 1
    while n < 2 * serve_kw["num_pages"]:
        pages = pg.reset_kg_rows(pages, pg.pad_page_ids(
            [min(i + 1, top) for i in range(n)]))
        n *= 2
    jax.block_until_ready(pages)
    del pages


def run_cell(spec, cell, conf, mix, limits, devs, peaks, *, seed: int,
             seconds: float, trace: bool, t_start: float, out_dir: Path,
             control: bool = False):
    """One run; returns (result line, stderr lines). With ``control`` the
    float8 reference takes the program's place in the comparison (the
    control, for setting limits; the benchmark's own runs never do), and
    the same limits judge it."""
    import jax
    from repro.serve.engine import DecodeEngine
    from repro.serve.scheduler import pages_needed
    from harness import trace as tr

    keys = [int(x) & 0x7FFFFFFF
            for x in np.random.SeedSequence(seed).generate_state(4)]
    cfg = program_config(conf)
    opts = decode_options(cfg, conf)
    params = make_params(cfg, jax.random.PRNGKey(keys[0]))
    jax.block_until_ready(params)
    traffic = spec.module("traffic", mix["kind"]).Traffic(
        mix, keys[1], cfg.vocab_size)
    ps = cfg.gate.block_size
    serve_kw = dict(
        n_slots=int(mix["clients"]), num_pages=int(mix["pool_pages"]),
        table_pages=pages_needed(mix["prompt_tokens"][1],
                                 mix["new_tokens"][1], ps),
        max_steps=10 ** 9, sample_seed=keys[2])
    eng = DecodeEngine(cfg, params, max_len=traffic.max_lifetime_tokens(),
                       options=opts)
    warm_up(eng, traffic, serve_kw, ps)

    trace_dir = out_dir / "trace" / cell["name"]
    t_trace: List[float] = []
    counter = CompileCounter()

    def on_open():
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            trace_dir.mkdir(parents=True, exist_ok=True)
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=tr.profile_options())
            t_trace.append(time.perf_counter())
        counter.armed = True

    feed = Feed(traffic, seconds, on_open=on_open)
    initial = feed.initial_requests()
    try:
        eng.serve(initial, arrivals=feed, on_token=feed.on_token, **serve_kw)
    except WindowClosed:
        pass
    else:
        raise RuntimeError("serve() ended before the window closed")
    t_stop = time.perf_counter()
    counter.armed = False
    trace_data = None
    if trace:
        jax.profiler.stop_trace()
        t_trace.append(t_stop)
        trace_data = tr.read(str(trace_dir))
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs)
    del eng
    run = Run(spec=spec, conf=conf, mix=mix, sessions=feed.sessions,
              t_start=t_start, t_open=feed.t_open, t_close=feed.t_close,
              t_stop=t_stop, n_slots=serve_kw["n_slots"], chips=len(devs),
              peaks=peaks, trace=trace_data,
              trace_window_s=(t_trace[1] - t_trace[0]) if trace else None)

    metrics, silent = {}, []
    for m in spec.metrics(cell["name"], trace):
        v = spec.module("metrics", m["name"]).reduce(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        else:
            silent.append(m["name"])

    sessions = list(feed.sessions.values())
    handed = [s for s in sessions if s.initial or s.t_handed is not None]
    failed = [s for s in handed
              if not s.tokens or (s.req is not None and s.req.status != "ok")]

    ref = spec.module("reference", conf["reference"])
    t_check = time.perf_counter()
    compared = "control (float8 reference)" if control else "program"
    got = chk.compare(ref, params, conf, mix, sessions,
                      np.random.default_rng(keys[3]),
                      "fp8" if control else "f32")
    check_s = time.perf_counter() - t_check
    # the cell's limits file names the numbers compared: served_gap_<stat>
    checks = {k: {"value": got[k[len("served_"):]], "limit": float(v)}
              for k, v in limits.items()}
    correct = bool(got["rows"] > 0 and checks and all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checks.values()))

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result: Dict[str, Any] = {"correct": correct, "attempted": len(handed),
                              "failed": len(failed), "metrics": metrics,
                              "device": device}
    if trace_data is not None:
        planes = trace_data.planes()
        device["busy_s"] = sum(trace_data.busy_ns(p) for p in planes) \
            / max(len(planes), 1) * 1e-9
        device["window_s"] = run.trace_window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in trace_data.top_ops(10)],
            "idle_gaps": [list(x) for x in
                          trace_data.idle_gaps(planes[0], 10)]
            if planes else []}
    result["checks"] = checks
    gaps = win.gaps(run)
    gap_max_ms = max(gaps) * 1e3 if gaps else float("nan")
    n_admitted = sum(1 for s in sessions if s.t_handed is not None
                     and win.in_window(s.t_handed, run))
    lines = [f"bench: cell={cell['name']} seed={seed} seconds={seconds} "
             f"trace={int(trace)} setup_s={run.t_open - t_start:.3f} "
             f"window_s={run.t_close - run.t_open:.3f} "
             f"attempted={len(handed)} failed={len(failed)} "
             f"compiles_in_window={len(counter.events)} "
             f"check_s={check_s:.1f} sessions_compared={got['sessions']}",
             f"window: tokens {len(win.window_tokens(run))} "
             f"admissions {n_admitted} gap_max_ms {gap_max_ms:.3f}",
             f"compared ({compared}): rows {got['rows']} "
             f"gap_mean {got['gap_mean']:.6g} gap_max {got['gap_max']:.6g} "
             f"agree {got['agree']:.4f}"]
    lines += [f"metric: {name} found nothing to read" for name in silent]
    lines += [f"check: {k} {c['value']:.6g} limit {c['limit']:.6g}"
              for k, c in checks.items()]
    return result, lines
