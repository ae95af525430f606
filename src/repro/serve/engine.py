"""Sparse decode serving engine.

Two serving paths share the SeerAttention-R machinery (block-selection
policy, budget/threshold selection, block-sparse decode kernel):

  * ``generate(batch, n)`` — the original uniform-batch path: one
    contiguous DecodeState, every row decodes in lockstep. Kept as the
    simple single-tenant API and as the parity reference for the paged
    path.
  * ``serve(requests)`` — continuous batching over a PAGED KV cache
    (serve.paging + serve.scheduler): iteration-level admission into free
    decode slots, per-row ragged lengths, retirement + page recycling the
    moment a request finishes. Pages are allocated LAZILY as decode
    crosses page boundaries (admission governed by current occupancy, not
    worst-case length) and pool exhaustion preempts the least-progressed
    request to host swap space instead of stalling — see ``serve()``'s
    ``admission`` parameter. The K-compression cache pages alongside
    the raw KV (page size == gate block size), so gate state can never
    desync from the cache under admission/eviction churn. One call is
    one ``serve.run.ServeRun``, whose methods are the loop's phases
    (docs/ARCHITECTURE.md, "The serve loop").

Decode behavior is configured by ONE static ``core.policy.DecodeOptions``
object (selection policy, kernel impl, sampling, budget) instead of
per-knob kwargs; the jitted steps close over it, so distinct options
compile distinct programs while runtime state never recompiles.
``serve()`` additionally takes cheap PER-REQUEST overrides: a
``"sampling"`` SamplingParams (per-request jitted sampler, hash-keyed
cache) and a ``"budget"`` token budget (runtime-masked per slot — no
recompilation). Tracks MEASURED per-batch sparsity from the actual
selected block mask and derived I/O savings either way.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation

from repro.config import ModelConfig
from repro.core.policy import DecodeOptions, default_options
from repro.models.registry import get_api
from repro.serve import sampling as smp
from repro.serve.eviction import EvictionConfig
from repro.serve.offload import SwapConfig
from repro.serve.run import (_IN_ACTIVE, _IN_BUDGET, _IN_CUR_LEN, _IN_TOKEN,
                             RESERVED_RIDS, ServeResult, ServeRun)


class GenerationResult(Dict):
    pass


class DecodeEngine:
    def __init__(self, cfg: ModelConfig, params: Any, *, max_len: int,
                 options: Optional[DecodeOptions] = None, shard=None):
        self.cfg = cfg
        self.params = params
        self.api = get_api(cfg)
        if self.api.decode_step_paged is None:
            # fail at construction, not deep inside serve(): the engine's
            # whole point is the paged path (ISSUE 10 satellite)
            raise ValueError(
                f"family {cfg.family!r}: no paged decode path "
                f"(ModelApi.decode_step_paged is None). Paged serving "
                f"covers the dense/moe/ssm/hybrid families; for a family "
                f"without it, run the contiguous api.prefill/decode_step "
                f"loop directly instead of DecodeEngine")
        if cfg.max_position_embeddings and \
                max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_len {max_len} > {cfg.arch_id}'s published "
                f"max_position_embeddings {cfg.max_position_embeddings}")
        self.max_len = max_len
        self.options = options if options is not None else default_options(cfg)
        self.options.check_platform()
        self.shard = shard          # mesh-aware: enables kernel_impl="sharded"
        # the decode state is donated: KV/Kg cache updates alias in place
        self._step = jax.jit(functools.partial(
            self._decode_step, options=self.options), donate_argnums=(1,))
        # paged decode steps, built lazily on first serve(): one program
        # per (track_evictions, per-slot budget column) flavor
        self._paged_steps: Dict[tuple, Any] = {}
        # serve()-path prefill, jitted per POWER-OF-TWO page bucket (ISSUE
        # 5: prompts are right-padded to the bucket, so the cache holds
        # O(log max_len) programs instead of one per distinct length)
        self._prefill_jit: Dict[int, Any] = {}
        self._last_aux = None       # measured selection of the latest step
        self._last_active = None    # serve(): slots active during that step

    def _decode_step(self, params, state, token, key=None, *,
                     options: DecodeOptions):
        logits, state, aux = self.api.decode_step(
            params, state, token, self.cfg, options=options,
            shard=self.shard)
        nxt = smp.sample(logits, options.sampling, key)
        return nxt, logits, state, aux

    def prefill(self, batch: Dict[str, jnp.ndarray], key=None):
        # stochastic sampling gets a fixed fallback key rather than an
        # error; to reproduce a generate() trajectory, pass the key chain
        # explicitly (generate splits its key before this call)
        if key is None and not self.options.sampling.greedy:
            key = jax.random.PRNGKey(0)
        # options ride along so metadata-reading policies (QuestPolicy) get
        # their selection-metadata cache bulk-built at prefill
        logits, state = self.api.prefill(self.params, batch, self.cfg,
                                         self.max_len,
                                         options=self.options)
        first = smp.sample(logits, self.options.sampling, key)
        return first, state

    def generate(self, batch: Dict[str, jnp.ndarray], n_tokens: int, *,
                 key: Optional[jax.Array] = None) -> GenerationResult:
        """Uniform-batch decode of ``n_tokens`` per row. ``key`` seeds the
        sampling chain when ``options.sampling`` is stochastic (defaults
        to PRNGKey(0)); greedy decoding never consumes randomness."""
        stochastic = not self.options.sampling.greedy
        if stochastic and key is None:
            key = jax.random.PRNGKey(0)
        self._last_aux = self._last_active = None   # stats reflect THIS run

        def next_key():
            nonlocal key
            if not stochastic:
                return None
            key, sub = jax.random.split(key)
            return sub

        t0 = time.perf_counter()
        token, state = self.prefill(batch, next_key())
        prefill_s = time.perf_counter() - t0
        toks = [token]
        t1 = time.perf_counter()
        for _ in range(n_tokens - 1):
            token, _, state, aux = self._step(self.params, state, token,
                                              next_key())
            self._last_aux = aux
            toks.append(token)
        jax.block_until_ready(token)
        decode_s = time.perf_counter() - t1
        out = jnp.stack(toks, axis=1)
        return GenerationResult(
            tokens=out, prefill_s=prefill_s, decode_s=decode_s,
            tok_per_s=(n_tokens - 1) * out.shape[0] / max(decode_s, 1e-9),
            final_len=state.cur_len)

    # -- continuous batching over paged KV ---------------------------------

    def serve(self, requests: Sequence[Dict[str, Any]], *,
              n_slots: int = 4, num_pages: Optional[int] = None,
              collect_logits: bool = False,
              max_steps: Optional[int] = None,
              sample_seed: int = 0, admission: str = "lazy",
              watermark: int = 0,
              eviction: Optional[EvictionConfig] = None,
              swap_config: Optional[SwapConfig] = None,
              faults=None, arrivals=None, on_token=None,
              table_pages: Optional[int] = None) -> ServeResult:
        """Continuous-batching decode over a paged KV cache.

        requests: each ``{"tokens": 1-D int array, "max_new_tokens": int}``
        plus optional per-request overrides — ``"rid"`` (id), ``"sampling"``
        (SamplingParams replacing ``options.sampling`` for that request),
        ``"budget"`` (token budget, applied as a runtime per-slot mask
        over the selected-block list; floored so the force-selected
        first/last blocks survive, and a cap beyond the compiled selection
        width is naturally a no-op), ``"tier"``/``"priority"``/``"reserve"``
        (SLO-tier fields, ISSUE 8: priority orders admission and protects
        against preemption; reserve=True gives THIS request the upfront
        full-lifetime page reservation under a lazy scheduler). Admission
        is priority-then-FIFO (plain FIFO when every priority is 0).

        Open-loop traffic (ISSUE 8): ``arrivals`` is an object with
        ``pull(step) -> list of request dicts`` and an ``exhausted``
        property (see serve.traffic.StepArrivals) — requests join the
        running batch mid-decode at their arrival step on the VIRTUAL
        clock (decode-loop iterations), so a fixed trace replays to
        bitwise-identical token streams. With ``arrivals``, ``requests``
        may be empty, and ``max_steps`` + ``table_pages`` (page-table
        width, >= any arriving request's lifetime pages) are REQUIRED —
        the engine cannot size them from an arrival process it has not
        drained. ``on_token(req, token, index, step)`` streams every
        generated token (prefill first token included) exactly once, in
        order, the moment it is appended — preempt/resume does not
        re-fire; ``step`` is the virtual clock it was produced at.

        ``admission`` picks the page-allocation policy (ISSUE 4):
        ``"lazy"`` (default) admits on CURRENT occupancy (prompt pages
        only), grows each slot's page list on demand as decode crosses
        page boundaries, and — when the pool runs dry — PREEMPTS the
        active request with the fewest generated tokens: its pages are
        swapped to a host buffer (serve.offload.HostSwapSpace) and the
        request is re-admitted later with its pages restored, resuming
        bitwise-identically. ``watermark`` pages are held back from lazy
        admission as growth headroom. ``"reserve"`` is the PR-1 upfront
        full-lifetime reservation (no growth, no preemption).

        Memory pressure & failure semantics (ISSUE 7):

        ``eviction`` — an ``EvictionConfig`` (or ``True`` for defaults)
        turns on RaaS-style PAGE eviction: when the pool runs dry, the
        coldest full pages of running requests (per-block attention
        recency/mass) are swapped out individually before any whole
        request is preempted; a step that selects an evicted page is
        detected via ``track_evictions`` telemetry, the page restored,
        and the step replayed — bitwise-equal to an unconstrained run
        (see serve.eviction). Requires lazy admission and a selective
        policy (the options layer validates).

        ``swap_config`` — a ``SwapConfig`` bounding the host swap tier in
        bytes, with optional spill-to-disk below it (LRU demotion).

        ``faults`` — a ``serve.faults.FaultInjector`` driving
        deterministic failures through the alloc/swap/disk/logits seams.
        Post-validation, serve() never raises for per-request trouble:
        a request that hits an unrecoverable fault (permanently
        unreadable swap entry, non-finite logits, admission stall,
        step-limit watchdog) is retired with ``status="error"`` and its
        PARTIAL tokens are still returned; the rest of the batch is
        bitwise-unaffected. ``stats["errors"]`` maps rid -> reason.

        Returns ``ServeResult``: rid -> generated token ids (length
        ``max_new_tokens``), ``res["stats"]`` has throughput, scheduler
        telemetry (incl. preemption/swap counters and clean-vs-preempted
        retirements) and measured per-request sparsity, and
        ``res["logits"]`` (rid -> [n, V] fp32, prefill token included)
        when ``collect_logits``.
        """
        if arrivals is not None:
            if max_steps is None:
                raise ValueError(
                    "arrivals requires an explicit max_steps — the engine "
                    "cannot bound the run from an undrained arrival process")
            if table_pages is None:
                raise ValueError(
                    "arrivals requires table_pages (page-table width >= any "
                    "arriving request's lifetime pages) — the engine cannot "
                    "size the table from an undrained arrival process")
        requests = list(requests)
        if not requests and arrivals is None:
            return ServeResult(stats={})
        rids = [rd.get("rid", i) for i, rd in enumerate(requests)]
        if len(set(rids)) != len(rids):
            raise ValueError(f"duplicate request ids: {sorted(rids)}")
        clash = set(rids) & set(RESERVED_RIDS)
        if clash:
            raise ValueError(f"request ids collide with reserved result "
                             f"keys: {clash}")
        self._last_aux = self._last_active = None   # stats reflect THIS run
        if eviction is True:
            eviction = EvictionConfig()
        if eviction is not None and admission != "lazy":
            raise ValueError(
                "eviction requires admission='lazy' (reserve admission "
                "never runs out of pages mid-flight)")
        run = ServeRun(
            self, requests, n_slots=n_slots, num_pages=num_pages,
            collect_logits=collect_logits, max_steps=max_steps,
            sample_seed=sample_seed, admission=admission,
            watermark=watermark, eviction=eviction, swap_config=swap_config,
            faults=faults, arrivals=arrivals, on_token=on_token,
            table_pages=table_pages)
        t0 = time.perf_counter()
        while run.has_work():
            with StepTraceAnnotation("serve.step", step_num=run.n_steps):
                run.arrive()
                run.admit()
                run.prepare()
                if not run.idle():
                    run.finish(run.settle(run.launch()))
        wall = time.perf_counter() - t0
        self._last_aux, self._last_active = run.last_aux, run.last_active
        return run.result(wall)

    def _paged_step(self, track: bool, use_budget: bool):
        """The jitted packed decode step ``serve()`` dispatches, one per
        (eviction tracking, per-slot budget column) flavor, cached on the
        engine so repeat ``serve()`` calls share compiled programs.

        ``step(params, pages, slot_state, step_in) -> (logits, pages,
        slot_state, aux, step_out)``: ``step_in`` is the packed int32
        ``[n_slots, lead + table_pages]`` input (``run._IN_*`` columns,
        then the page-table row), ``step_out`` the packed int32 ``[n_slots,
        2 or 4]`` output the host pulls once (``run._OUT_*``). The pools
        are donated; ``slot_state`` is not (a replay re-runs from it)."""
        step = self._paged_steps.get((track, use_budget))
        if step is not None:
            return step
        cfg, measure = self.cfg, self.options.measure_sparsity
        # validates policy/schedule compatibility with eviction
        # (reads_full_kv, dense-staged layers — see DecodeOptions)
        options = (self.options.replace(track_evictions=True) if track
                   else self.options)
        lead = _IN_BUDGET + 1 if use_budget else _IN_BUDGET

        def paged_decode_step(params, pages, slot_state, step_in):
            logits, pages, slot_state, aux = self.api.decode_step_paged(
                params, pages, slot_state, step_in[:, _IN_TOKEN],
                step_in[:, lead:], step_in[:, _IN_CUR_LEN],
                step_in[:, _IN_ACTIVE].astype(bool), cfg=cfg,
                options=options,
                budget_blocks=(step_in[:, _IN_BUDGET] if use_budget
                               else None),
                shard=self.shard)
            # what the host reads of every step, in one small array
            cols = [jnp.argmax(logits, axis=-1).astype(jnp.int32),
                    jnp.isfinite(logits).all(axis=-1).astype(jnp.int32)]
            if measure:
                cols += [jax.lax.bitcast_convert_type(
                    aux[k].astype(jnp.float32), jnp.int32)
                    for k in ("sparsity_rows", "sel_blocks")]
            return logits, pages, slot_state, aux, jnp.stack(cols, -1)
        # named, so the device trace shows jit_paged_decode_step
        step = self._paged_steps[track, use_budget] = jax.jit(
            paged_decode_step, donate_argnums=(1,))
        return step

    def _prefill_fn(self, bucket: int):
        """The jitted prefill of one power-of-two page bucket, cached on
        the engine."""
        fn = self._prefill_jit.get(bucket)
        if fn is None:
            max_len = bucket * self.cfg.gate.block_size

            def paged_prefill(params, batch):
                return self.api.prefill(params, batch, cfg=self.cfg,
                                        max_len=max_len,
                                        options=self.options)
            # named, so the device trace shows jit_paged_prefill
            fn = self._prefill_jit[bucket] = jax.jit(paged_prefill)
        return fn

    def sparsity_stats(self, state=None) -> Dict[str, Any]:
        """Measured selection economics of the LATEST decode step.

        Sparsity comes from the step's ACTUAL selected block mask
        (``core.sparsity.sparsity_ratio`` inside the decode step, averaged
        over layers), not from the configured budget — threshold-method
        adaptivity, ragged batches and per-request budget overrides are
        all reflected. ``sparsity_rows`` is the per-batch-row breakdown.
        Derived I/O terms follow the paper Fig. 6 model. Before any decode
        step has run there is nothing to measure: returns the SAME key
        set with neutral values and ``measured=False``. ``state`` is
        accepted for backward compatibility and unused."""
        cfg = self.cfg
        if self._last_aux is None or not self.options.measure_sparsity:
            sel, vis, rho = 0.0, 0.0, 0.0
            rows = np.zeros((0,), np.float32)
            measured = False
        else:
            aux = jax.device_get(self._last_aux)
            rows = np.asarray(aux["sparsity_rows"], np.float32)
            sel_rows = np.asarray(aux["sel_blocks"], np.float32)
            vis_rows = np.asarray(aux["vis_blocks"], np.float32)
            if self._last_active is not None:   # paged: skip idle slots
                act = np.asarray(self._last_active, bool)
                rows, sel_rows, vis_rows = \
                    rows[act], sel_rows[act], vis_rows[act]
            sel = float(np.mean(sel_rows))
            vis = float(np.mean(vis_rows))
            # the aux scalar is mean(rows) by construction; recompute it
            # over the surviving rows
            rho = float(np.mean(rows))
            measured = True
        return {
            "sparsity": rho, "sparsity_rows": rows,
            "sel_blocks": sel, "vis_blocks": vis,
            "io_speedup": (vis / sel) if sel > 0 else 1.0,
            "kv_bytes_read": sel * cfg.gate.block_size
            * cfg.n_kv_heads * cfg.resolved_head_dim * 2 * 2,
            "gate_overhead_frac": (cfg.gate.d_gate / cfg.gate.block_size)
            / (2 * cfg.resolved_head_dim),
            "measured": measured,
        }
