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
    desync from the cache under admission/eviction churn.

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
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.config import ModelConfig
from repro.core.policy import DecodeOptions, default_options
from repro.models.registry import get_api
from repro.serve import paging as pg
from repro.serve import sampling as smp
from repro.serve import slotstate as ss
from repro.serve.eviction import EvictionConfig, EvictionManager
from repro.serve.offload import (HostSwapSpace, SwapConfig, SwapEntry,
                                 SwapError)
from repro.serve.scheduler import Request, Scheduler, pages_needed


def _sync(what: str) -> TraceAnnotation:
    """Span of one blocking device->host pull: ``serve()`` wraps each round
    trip in its own, so the trace counts them."""
    return TraceAnnotation("serve.sync", what=what)


def _pull(a, what: str):
    """A device array as a host copy, in its own ``serve.sync`` span (None
    passes through)."""
    if a is None:
        return None
    with _sync(what):
        return np.array(a)


# serve()'s decode step takes one packed int32 input per step, one row per
# slot: these leading columns (the budget column only when some request
# sets a budget), then the slot's page-table row
_IN_TOKEN, _IN_CUR_LEN, _IN_ACTIVE, _IN_BUDGET = range(4)
# and returns one packed int32 [n_slots, 2 or 4] array that the host pulls
# once: the greedy token, whether the logits row is finite and, under
# measure_sparsity, the float32 bits of the row's sparsity and selected
# block count
_OUT_NEXT, _OUT_FINITE, _OUT_RHO, _OUT_SEL = range(4)


def _bucket_pages(prompt_len: int, ps: int) -> int:
    """Prefill bucket of a prompt: its page count rounded up to a power of
    two."""
    return 1 << (-(-prompt_len // ps) - 1).bit_length()


class GenerationResult(Dict):
    pass


class ServeResult(Dict):
    """rid -> list of generated token ids, plus throughput/stats fields
    under the ``stats`` key (dict access, like GenerationResult)."""
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
        cfg = self.cfg
        ps = cfg.gate.block_size
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

        reqs: list = []
        sampling_of: Dict[Any, Any] = {}
        budget_of: Dict[Any, Any] = {}
        ridx_of: Dict[Any, int] = {}
        rho_sum: Dict[Any, float] = {}
        sel_sum: Dict[Any, float] = {}
        rho_n: Dict[Any, int] = {}
        rejected_arrivals = 0

        def register(rd: Dict[str, Any]) -> Request:
            """One request dict -> a tracked Request. ALL per-request
            bookkeeping (sampling/budget overrides, the fold_in index that
            keys the stochastic sampling chain, sparsity accumulators) is
            created here, so upfront and mid-decode arrivals share one
            path; registration ORDER fixes the sampling keys, which is
            deterministic for a fixed request list + trace."""
            req = Request(
                rid=rd.get("rid", len(reqs)),
                prompt=np.asarray(rd["tokens"], np.int32).reshape(-1),
                max_new_tokens=int(rd["max_new_tokens"]),
                tier=str(rd.get("tier", "default")),
                priority=int(rd.get("priority", 0)),
                admit_reserve=bool(rd.get("reserve", False)))
            reqs.append(req)
            sampling_of[req.rid] = rd.get("sampling") or self.options.sampling
            budget_of[req.rid] = rd.get("budget")
            ridx_of[req.rid] = len(ridx_of)
            rho_sum[req.rid] = sel_sum[req.rid] = 0.0
            rho_n[req.rid] = 0
            return req

        for rd in requests:
            register(rd)
        if not reqs and arrivals is None:
            return ServeResult(stats={})
        rids = [r.rid for r in reqs]
        if len(set(rids)) != len(rids):
            raise ValueError(f"duplicate request ids: {sorted(rids)}")
        clash = set(rids) & {"stats", "logits"}
        if clash:
            raise ValueError(f"request ids collide with reserved result "
                             f"keys: {clash}")
        base_key = jax.random.PRNGKey(sample_seed)
        self._last_aux = self._last_active = None   # stats reflect THIS run

        if eviction is True:
            eviction = EvictionConfig()
        eviction_options = self.options
        if eviction is not None:
            if admission != "lazy":
                raise ValueError(
                    "eviction requires admission='lazy' (reserve admission "
                    "never runs out of pages mid-flight)")
            # validates policy/schedule compatibility up front
            # (reads_full_kv, dense-staged layers — see DecodeOptions)
            eviction_options = self.options.replace(track_evictions=True)

        npt = max([pages_needed(r.prompt_len, r.max_new_tokens, ps)
                   for r in reqs]
                  + ([int(table_pages)] if table_pages is not None else []))
        if num_pages is None:
            # enough for every slot to hold a worst-case sequence (+null)
            num_pages = n_slots * npt + 1
        sched = Scheduler(n_slots, num_pages, ps, npt,
                          admission=admission, watermark=watermark,
                          eviction_enabled=eviction is not None,
                          faults=faults)
        sched.on_token = on_token
        swap = HostSwapSpace(config=swap_config, faults=faults)
        for r in reqs:
            sched.submit(r)

        # per-slot selected-block caps: ONLY active when some request sets
        # a "budget" (otherwise no mask exists at all — zero risk of
        # clipping a policy whose list is wider than the config budget).
        # Slots without an override get a never-binding sentinel; override
        # caps CEIL to blocks (a request never gets fewer tokens of
        # attention than it asked for — the same rounding as
        # DecodeOptions.max_selected) and are floored so the force-selected
        # first/last blocks (which rank ahead of every scored block by
        # construction) survive.
        # with open-loop arrivals the mask must exist up front: whether a
        # LATER arrival carries a budget override cannot retroactively
        # change the compiled step's signature mid-run
        use_budget = (arrivals is not None
                      or any(b is not None for b in budget_of.values()))
        no_cap = np.int32(2 ** 30)
        floor = max(1, int(cfg.gate.always_first_block)
                    + int(cfg.gate.always_last_block))
        budget_blocks = (np.full((n_slots,), no_cap, np.int32)
                         if use_budget else None)

        def slot_cap(rid) -> int:
            b = budget_of[rid]
            if b is None:
                return int(no_cap)
            return max(floor, -(-int(b) // ps))

        # host-side per-slot sampling runs ONLY while a LIVE request is
        # stochastic; otherwise (and again once every stochastic request
        # retires) the device-side batched argmax transfers n_slots ints,
        # not [n_slots, V] logits. The stochastic path pays one tiny
        # dispatch per active slot per step — batching slots that share
        # SamplingParams (vmapped keys) is a serving-scale follow-up.
        def any_stochastic(slot_reqs) -> bool:
            return any(not sampling_of[slot_reqs[s].rid].greedy
                       for s in np.nonzero(sched.active)[0])

        def sample_slot(req, row_logits) -> int:
            """Sample one slot's next token with the request's params."""
            params_s = sampling_of[req.rid]
            if params_s.greedy:
                return int(np.argmax(row_logits))
            key = jax.random.fold_in(
                jax.random.fold_in(base_key, ridx_of[req.rid]),
                len(req.out_tokens))
            with _sync("sample"):
                return int(smp.make_sampler(params_s)(
                    jnp.asarray(row_logits), key=key))

        # how many layer slices the pools carry is a FAMILY property
        # (transformer: self-attn layers; hybrid: attention units; ssm: 0
        # — zero-size pools), not a params-shape hack
        nl = self.api.paged_attn_layers(cfg)
        # min/max metadata pools only for the policy that reads them
        # (needs_meta is part of the SelectionPolicy protocol)
        ghosts = 0
        if eviction is not None:
            ghosts = (eviction.ghost_rows if eviction.ghost_rows is not None
                      else n_slots * npt)
        pages = pg.init_pages(cfg, num_pages, nl,
                              with_meta=self.options.policy.needs_meta,
                              ghost_rows=ghosts,
                              quantize=self.options.quantize)
        # per-slot recurrent state (PR 10): the page pools' lifecycle twin
        # for recurrent families — None (an empty pytree) for pages-only
        # families, so the step jit sees zero extra operands
        slot_state = (None if self.api.init_slot_state is None
                      else self.api.init_slot_state(cfg, n_slots))
        mesh = getattr(self.shard, "mesh", None)
        if mesh is not None and self.options.kernel_impl == "sharded":
            # paged x sharded: keep the pools resident head-sharded so the
            # per-step shard_map never reshards pool-sized arrays
            from jax.sharding import NamedSharding
            from repro.distributed.sharding import paged_pool_pspecs
            pages = jax.device_put(pages, jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                paged_pool_pspecs(pages, mesh)))
        track = eviction is not None
        # leading columns of the packed step input, before the page table
        lead = _IN_BUDGET + 1 if use_budget else _IN_BUDGET
        step = self._paged_steps.get((track, use_budget))
        if step is None:   # one jit per flavor per engine: repeat serve()
            measure = self.options.measure_sparsity

            def paged_decode_step(params, pages, slot_state, step_in):
                logits, pages, slot_state, aux = self.api.decode_step_paged(
                    params, pages, slot_state, step_in[:, _IN_TOKEN],
                    step_in[:, lead:], step_in[:, _IN_CUR_LEN],
                    step_in[:, _IN_ACTIVE].astype(bool), cfg=cfg,
                    options=eviction_options,
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
        evmgr = None
        if eviction is not None:
            evmgr = EvictionManager(
                sched, swap, num_phys=num_pages, ghost_rows=ghosts,
                page_size=ps,
                page_bytes=EvictionManager.page_restore_bytes(pages),
                always_first_block=cfg.gate.always_first_block,
                config=eviction)

        token_buf = np.zeros((n_slots,), np.int32)
        active_sum = active_max = idle_spins = 0
        n_steps = 0
        t0 = time.perf_counter()
        limit = max_steps if max_steps is not None else sum(
            r.max_new_tokens for r in reqs) + len(reqs) + 8

        # requests whose swap-out/restore hit a permanent fault inside a
        # scheduler callback (where failing in place would corrupt the
        # preemption bookkeeping) — failed right after the callback chain
        # unwinds, before the next step runs
        pending_failures: list = []

        def fail_req(req: Request, reason: str) -> None:
            sched.fail(req, reason)
            swap.discard(req.rid)

        def flush_failures() -> None:
            while pending_failures:
                req, reason = pending_failures.pop()
                if req.rid not in sched.finished:
                    fail_req(req, reason)

        def swap_out(req: Request) -> None:
            """Preemption callback: capture the victim's device pages (and
            its pending token) into host swap space BEFORE they are freed.
            ``req.pages`` is in logical order, so restore is a plain
            scatter. Only CONTENT pages are captured — a growth page
            allocated for the not-yet-written next token is dropped (it is
            empty; re-admission re-grows it), keeping the swap footprint
            equal to what re-admission will allocate.

            Preempt x evict merge: blocks of the victim that page eviction
            already moved to host swap are stitched back into the single
            SwapEntry from their PageEntries (the device ghost rows only
            mirror gate/meta state; K/V truth for an evicted page lives on
            the host), so resume takes the unchanged — bitwise-pinned —
            whole-request restore path. A permanent swap fault here marks
            the victim failed instead of raising through the scheduler.

            Recurrent families (PR 10): the victim's per-layer recurrent
            rows ride along in the entry (``state_conv``/``state_h``) —
            captured from the PRE-step buffer (the step jit never donates
            ``slot_state``), which together with the pending ``token`` is
            exactly the point decode resumes from."""
            with TraceAnnotation("serve.swap_out", rid=req.rid):
                n_content = max(1, -(-req.swap_len // ps))
                content = req.pages[:n_content]
                # ghost ids carry no K/V — extract through the trash page
                # and overwrite those blocks from their host PageEntries
                # below
                phys_ids = [p if p < num_pages else pg.NULL_PAGE
                            for p in content]
                # power-of-two id padding (trash-page ids): bounds the jit
                # cache of extract/restore to O(log pool) programs;
                # re-admission pads the same n_content to the same bucket,
                # so shapes match
                k, v, kg, kmin, kmax, k_sc, v_sc = pg.extract_pages(
                    pages, pg.pad_page_ids(phys_ids))
                k, v, kg, kmin, kmax, k_sc, v_sc = (
                    _pull(a, f"swap_{what}") for a, what in (
                        (k, "k"), (v, "v"), (kg, "kg"), (kmin, "kmin"),
                        (kmax, "kmax"), (k_sc, "k_scale"), (v_sc, "v_scale")))
                reason = None
                if evmgr is not None:
                    blocks = evmgr.evicted.pop(req.rid, None) or {}
                    for lb, ghost in sorted(blocks.items()):
                        evmgr.ghost_free.append(ghost)
                        try:
                            pe = swap.pop(("page", req.rid, lb))
                        except SwapError:
                            reason = "restore_failed"
                            continue
                        k[:, lb] = pe.k[:, 0]
                        v[:, lb] = pe.v[:, 0]
                        if kg is not None and pe.kg is not None:
                            kg[:, lb] = pe.kg[:, 0]
                        if kmin is not None and pe.kmin is not None:
                            kmin[:, lb] = pe.kmin[:, 0]
                            kmax[:, lb] = pe.kmax[:, 0]
                        if k_sc is not None and pe.k_scale is not None:
                            k_sc[:, lb] = pe.k_scale[:, 0]
                            v_sc[:, lb] = pe.v_scale[:, 0]
                st_conv = st_h = None
                if slot_state is not None:
                    row = ss.read_slot(slot_state, jnp.asarray(req.slot))
                    st_conv = _pull(row.conv, "swap_conv")
                    st_h = _pull(row.h, "swap_h")
                if reason is None:
                    try:
                        swap.put(req.rid, SwapEntry(
                            k=k, v=v, kg=kg,
                            token=int(token_buf[req.slot]),
                            cur_len=req.swap_len, kmin=kmin, kmax=kmax,
                            k_scale=k_sc, v_scale=v_sc,
                            state_conv=st_conv, state_h=st_h))
                    except SwapError:
                        reason = "swap_put_failed"
                if reason is not None:
                    pending_failures.append((req, reason))

        # recycled pages may hold a previous tenant's Kg row; the
        # staleness contract needs a ZERO row on every partial trailing
        # page. Freed pages are tracked in `dirty` and zeroed in one
        # batched call per release iteration (cheap), so the per-step
        # growth path almost never pays a device dispatch: admission
        # reuse is cleaned by scatter_prefill/restore anyway, and growth
        # only re-zeroes a page freed by a preemption in the SAME
        # iteration (LIFO reuse before the end-of-iteration sweep).
        dirty: set = set()
        # reserve admission never grows: every reuse goes through
        # scatter_prefill (which zeroes the Kg/meta rows itself) — no sweeps
        gate_paged = admission == "lazy" and (
            pages.kg_pages is not None or pages.kmin_pages is not None
            or pages.k_scale_pages is not None)

        def sweep_dirty(ids) -> None:
            nonlocal pages, dirty
            if ids and gate_paged:
                pages = pg.reset_kg_rows(pages, pg.pad_page_ids(sorted(ids)))
            dirty.difference_update(ids)

        def mark_live(ids) -> None:
            """Pages just (re)written with live content: pull them out of
            both pending-zero queues so a later sweep cannot clobber the
            fresh gate rows (a page can be freed and reused within one
            iteration — retire-at-admission, eviction, replay restore)."""
            live = set(ids)
            dirty.difference_update(live)
            sched.released = [p for p in sched.released if p not in live]

        if evmgr is not None:
            def evict_cb(n: int) -> int:
                nonlocal pages
                pages, freed = evmgr.evict(pages, n)
                return freed

            def release_filter(req: Request):
                # heat rows are per-slot state; the slot is being vacated
                if req.slot >= 0 and sched.slots[req.slot] is req:
                    evmgr.heat.reset_row(req.slot)
                evmgr.forget(req)    # drop host entries, reclaim ghosts
                return [p for p in req.pages if p < num_pages]

            sched.evict_cb = evict_cb
            sched.release_filter = release_filter
            evmgr.mark_clean = mark_live

        def restore(req: Request, entry: SwapEntry):
            """Resume: scatter a swapped-out request's pages (and its
            recurrent rows) back from host swap space into its new pages
            and slot. Returns (pages, slot_state)."""
            def dev(a):
                return None if a is None else jnp.asarray(a)
            new_pages = pg.restore_pages(
                pages, dev(entry.k), dev(entry.v), dev(entry.kg),
                pg.pad_page_ids(req.pages), dev(entry.kmin), dev(entry.kmax),
                k_scale=dev(entry.k_scale), v_scale=dev(entry.v_scale))
            if slot_state is None or (entry.state_conv is None
                                      and entry.state_h is None):
                return new_pages, slot_state
            row = ss.SlotState(conv=dev(entry.state_conv),
                               h=dev(entry.state_h))
            return new_pages, ss.write_slot(slot_state, row,
                                            jnp.asarray(req.slot))

        def fail_unfinished(reason: str) -> None:
            for r in reqs:
                if r.rid not in sched.finished:
                    fail_req(r, reason)

        while sched.has_work() or (arrivals is not None
                                   and not arrivals.exhausted):
            with StepTraceAnnotation("serve.step", step_num=n_steps):
                # the scheduler's virtual clock: lifecycle ``*_step`` stamps
                # and the arrival schedule both read the decode-loop iteration
                # counter, never wall time — fixed trace => fixed schedule
                sched.now = n_steps
                if arrivals is not None:
                    with TraceAnnotation("serve.arrivals") as span:
                        handed = arrivals.pull(n_steps)
                        span.set_metadata(handed=len(handed))
                        for rd in handed:
                            rid = rd.get("rid", len(reqs))
                            if rid in ridx_of or rid in ("stats", "logits"):
                                # malformed trace entry: drop it (never-
                                # raises — the already-running batch must
                                # not pay for it)
                                rejected_arrivals += 1
                                continue
                            req = register(rd)
                            try:
                                sched.submit(req)
                            except ValueError as e:
                                # an arriving request the pool/table can
                                # never hold fails ALONE with the reason,
                                # mid-run
                                sched.fail(req, f"submit_rejected: {e}")
                for req in sched.admissions():
                    with TraceAnnotation(
                            "serve.admit", rid=req.rid,
                            prompt_len=req.prompt_len,
                            **({"resumed": True} if req.swapped else
                               {"bucket": _bucket_pages(req.prompt_len, ps)})):
                        if req.swapped:        # resume: restore, don't prefill
                            try:
                                entry = swap.pop(req.rid)
                            except SwapError:
                                # permanently unreadable swap entry: the
                                # request's KV is gone — fail IT, keep
                                # serving the others
                                fail_req(req, "restore_failed")
                                continue
                            with TraceAnnotation("serve.restore", rid=req.rid):
                                pages, slot_state = restore(req, entry)
                            token_buf[req.slot] = entry.token
                            req.swapped = False
                        else:
                            pages, slot_state, lg = self._paged_prefill(
                                pages, slot_state, req, ps)
                            first = sample_slot(req, lg)
                            req.out_tokens.append(first)
                            sched.note_token(req, first)  # TTFT stamp + stream
                            if collect_logits:
                                req.out_logits.append(lg)
                            token_buf[req.slot] = first
                        mark_live(req.pages)             # content written
                        if budget_blocks is not None:
                            budget_blocks[req.slot] = slot_cap(req.rid)
                        sched.retire_if_done(req)
                with TraceAnnotation("serve.prepare"):
                    if evmgr is not None:
                        pages = evmgr.enforce_caps(pages)
                    # lazy growth + preemption
                    fresh = sched.prepare_step(swap_out)
                    flush_failures()
                    dirty.update(sched.drain_released())
                    sweep_dirty([p for p in fresh if p in dirty])
                if not sched.active.any():
                    if not sched.pending:
                        if arrivals is not None and not arrivals.exhausted:
                            # open-loop gap: nothing to decode yet but the
                            # trace has more arrivals — tick the virtual clock
                            # forward so they come due (bounded by max_steps)
                            n_steps += 1
                            if n_steps > limit:
                                fail_unfinished("step_limit")
                                break
                            continue
                        break
                    # preemption may have just vacated every slot while freeing
                    # its pages — loop back through admissions once before
                    # declaring a stall
                    idle_spins += 1
                    if idle_spins > 1:
                        # no-progress watchdog: admission is stuck (e.g. the
                        # allocator keeps faulting). Fail the request admission
                        # keeps choosing (highest priority, FIFO within the
                        # class) — each firing unblocks the queue by one, so
                        # the loop always terminates — instead of raising away
                        # everyone's partial results.
                        fail_req(max(sched.pending, key=lambda r: r.priority),
                                 "admission_stall")
                        idle_spins = 0
                    continue
                idle_spins = 0
                active_now = int(sched.active.sum())
                active_sum += active_now
                active_max = max(active_max, active_now)
                replays = 0
                while True:
                    # slot_state is NOT donated and NOT adopted until the step
                    # is accepted: a faulted attempt is re-run from the SAME
                    # recurrent state (updates are not idempotent), which keeps
                    # the replay bitwise-equal to a never-faulted step
                    with TraceAnnotation("serve.dispatch", active=active_now):
                        # one upload: a fresh host array every attempt (the
                        # device may alias it while the step is in flight)
                        step_in = np.empty(
                            (n_slots, lead + sched.page_table.shape[1]),
                            np.int32)
                        step_in[:, _IN_TOKEN] = token_buf
                        step_in[:, _IN_CUR_LEN] = sched.cur_len
                        step_in[:, _IN_ACTIVE] = sched.active
                        if budget_blocks is not None:
                            step_in[:, _IN_BUDGET] = budget_blocks
                        step_in[:, lead:] = sched.page_table
                        logits, pages, slot_state_out, aux, step_out = step(
                            self.params, pages, slot_state,
                            jnp.asarray(step_in))
                    if evmgr is None:
                        break
                    with _sync("touched_pages"):
                        touched = np.asarray(aux["touched_pages"], bool)
                    faulted = (touched & (sched.page_table >= num_pages)
                               & sched.active[:, None])
                    if not faulted.any():
                        # victim model feeds on FAULT-FREE steps only (replay
                        # reads are restore traffic, not attention heat)
                        evmgr.heat.observe(touched, sched.active)
                        break
                    # optimistic execution faulted: some row selected a block
                    # whose K/V is evicted (its gate/meta ghost rows scored it
                    # normally). Restore the pages and RE-RUN the step; page
                    # writes are idempotent (the trailing append rewrites the
                    # same values at the same positions before any read), so
                    # the replay is bitwise equal to a never-faulted step.
                    evmgr.n_replays += 1
                    replays += 1
                    faulted_slots = np.nonzero(faulted.any(axis=1))[0]
                    if replays > evmgr.config.max_replays:
                        # evict/restore thrash: fail the faulted requests. The
                        # surviving rows of this run never read a ghost, so
                        # their logits are valid as-is.
                        for slot in faulted_slots:
                            if sched.slots[slot] is not None:
                                fail_req(sched.slots[slot], "restore_thrash")
                        break
                    with TraceAnnotation(
                            "serve.replay", replay=replays,
                            rid=[sched.slots[s].rid for s in faulted_slots
                                 if sched.slots[s] is not None]):
                        # pin every page ANY active row touched (plus
                        # trailing): restoring row A must not evict what
                        # row B's replay reads, or the replay loop could
                        # ping-pong forever
                        pinned = set()
                        for slot in np.nonzero(sched.active)[0]:
                            r = sched.slots[slot]
                            for lb in np.nonzero(touched[slot])[0]:
                                pinned.add((r.rid, int(lb)))
                            pinned.add((r.rid,
                                        int(sched.cur_len[slot]) // ps))
                        for slot in faulted_slots:
                            r = sched.slots[slot]
                            if r is None or not sched.active[slot]:
                                continue  # preempted restoring another row
                            lbs = [int(x)
                                   for x in np.nonzero(faulted[slot])[0]]
                            pages, ok = evmgr.restore(pages, r, lbs,
                                                      pinned=pinned,
                                                      swap_out=swap_out)
                            if not ok:
                                fail_req(r, "restore_failed")
                        flush_failures()
                        dirty.update(sched.drain_released())
                    if not sched.active.any():
                        break
                # the attempt that broke the loop is the accepted one (fault-
                # free, or its surviving rows' outputs are valid); slots that
                # failed/retired/preempted get their rows rewritten at the
                # next admission or restore before anything reads them
                slot_state = slot_state_out
                if not sched.active.any():
                    # every row failed or was preempted mid-replay; count the
                    # spin against the step limit so injected-fault storms
                    # still terminate
                    n_steps += 1
                    if n_steps > limit:
                        fail_unfinished("step_limit")
                        break
                    continue
                self._last_aux = aux
                # idle/retired slots decode garbage rows (rho=0): remember who
                # was live so sparsity_stats() averages ACTIVE rows only
                self._last_active = sched.active.copy()
                slot_reqs = list(sched.slots)   # before retirement mutates it
                # the step's one blocking pull: greedy tokens, finite
                # flags and the sparsity rows, packed on the device
                with _sync("step_out"):
                    out_np = np.asarray(step_out)
                # per-request failure isolation: a non-finite logits row (a
                # poisoned request, or an injected "logits" fault) is retired
                # with an error instead of sampling garbage into the batch
                finite = out_np[:, _OUT_FINITE] != 0
                if faults is not None and faults.fire("logits"):
                    act = np.nonzero(sched.active)[0]
                    if act.size:
                        finite[act[0]] = False
                bad = (~finite) & sched.active
                for slot in np.nonzero(bad)[0]:
                    fail_req(sched.slots[slot], "non_finite_logits")
                stoch = any_stochastic(slot_reqs)
                lg_np = None
                if collect_logits or stoch:
                    with _sync("logits"):
                        lg_np = np.asarray(logits, np.float32)
                if stoch:
                    with TraceAnnotation("serve.sample"):
                        nxt = np.zeros((n_slots,), np.int32)
                        for slot in np.nonzero(sched.active)[0]:
                            nxt[slot] = sample_slot(slot_reqs[slot],
                                                    lg_np[slot])
                else:
                    nxt = out_np[:, _OUT_NEXT]
                if self.options.measure_sparsity:
                    rho_rows = out_np[:, _OUT_RHO].view(np.float32)
                    sel_rows = out_np[:, _OUT_SEL].view(np.float32)
                    for slot in np.nonzero(sched.active)[0]:
                        rid = slot_reqs[slot].rid
                        rho_sum[rid] += float(rho_rows[slot])
                        sel_sum[rid] += float(sel_rows[slot])
                        rho_n[rid] += 1
                with TraceAnnotation("serve.complete"):
                    sched.complete_step(nxt,
                                        lg_np if collect_logits else None)
                    # retirements this step
                    dirty.update(sched.drain_released())
                    sweep_dirty(set(dirty))
                    token_buf = np.where(sched.active, nxt, 0).astype(
                        np.int32)
                n_steps += 1
                if n_steps > limit:
                    # step-limit watchdog: fail whatever is unfinished with
                    # partial results + telemetry instead of raising away the
                    # finished requests' outputs
                    fail_unfinished("step_limit")
                    break
        wall = time.perf_counter() - t0

        out = ServeResult()
        for r in reqs:
            out[r.rid] = r.out_tokens
        if collect_logits:
            out["logits"] = {r.rid: np.stack(r.out_logits)
                             for r in reqs if r.out_logits}
        gen_toks = sum(len(r.out_tokens) for r in reqs)
        # slot_util over DECODE-step tokens only (each admission's first
        # token comes from prefill, not from a decode slot)
        decode_toks = gen_toks - sched.n_admitted
        # "retired" counts every finished request; requests that were
        # preempted at least once along the way are broken out separately
        # (ISSUE 4 bugfix: the two used to be indistinguishable)
        retired_preempted = sum(1 for r in sched.finished.values()
                                if r.n_preemptions > 0)
        out["stats"] = {
            "wall_s": wall, "decode_steps": n_steps,
            "generated_tokens": gen_toks,
            "tok_per_s": gen_toks / max(wall, 1e-9),
            "slot_util": decode_toks / max(n_steps * n_slots, 1),
            "admitted": sched.n_admitted, "retired": sched.n_retired,
            "retired_clean": sched.n_retired - retired_preempted,
            "retired_preempted": retired_preempted,
            "admission_stalls": sched.admission_stalls,
            "admission": admission, "watermark": watermark,
            "preemptions": sched.n_preemptions,
            "resumed": sched.n_resumed,
            "swapped_out_bytes": swap.bytes_out,
            "swapped_in_bytes": swap.bytes_in,
            # ISSUE 7: failure isolation + memory-pressure telemetry
            "failed": sched.n_failed,
            "errors": {r.rid: r.error for r in sched.finished.values()
                       if r.status != "ok"},
            "swap": swap.stats(),
            "faults": None if faults is None else faults.stats(),
            "evictions": 0 if evmgr is None else evmgr.n_evicted,
            "page_restores": 0 if evmgr is None else evmgr.n_page_restores,
            "replay_steps": 0 if evmgr is None else evmgr.n_replays,
            "mean_active_slots": active_sum / max(n_steps, 1),
            "max_active_slots": active_max,
            "peak_pages_used": (sched.allocator.num_pages - 1
                                - sched.allocator.min_free),
            "num_pages": num_pages, "page_size": ps,
            # devices the K/V page pool spans (the kv-head shards of the
            # paged x sharded path; 1 unsharded)
            "pool_devices": len(pages.k_pages.sharding.device_set),
            # bucketed-prefill jit cache (bounded: one program per
            # power-of-two page count ever seen by this engine)
            "prefill_jit_programs": len(self._prefill_jit),
            "prefill_buckets_pages": sorted(self._prefill_jit),
            # measured per-request selection telemetry (decode steps only;
            # empty — not zero — when telemetry is compiled out)
            "sparsity_by_rid": {rid: rho_sum[rid] / rho_n[rid]
                                for rid in rho_sum if rho_n[rid]},
            "sel_blocks_by_rid": {rid: sel_sum[rid] / rho_n[rid]
                                  for rid in sel_sum if rho_n[rid]},
            # ISSUE 8: per-request lifecycle (``*_step`` on the virtual
            # clock — deterministic TTFT/TPOT proxies; ``t_*`` wall-clock
            # seconds, -1.0 where the stage was never reached)
            "timing_by_rid": {r.rid: {
                "submit_step": r.submit_step,
                "admit_step": r.admit_step,
                "first_token_step": r.first_token_step,
                "retire_step": r.retire_step,
                "t_submit": r.t_submit, "t_admit": r.t_admit,
                "t_first": r.t_first, "t_retire": r.t_retire,
                "n_tokens": len(r.out_tokens)} for r in reqs},
            "tier_by_rid": {r.rid: r.tier for r in reqs},
            "rejected_arrivals": rejected_arrivals,
        }
        return out

    def _paged_prefill(self, pages: pg.PagedPages, slot_state,
                       req: Request, ps: int):
        """Contiguous prefill of one request, scattered into its pages.

        Prompt lengths are rounded UP to power-of-two page buckets (ISSUE
        5 satellite): tokens are right-padded to the bucket width and the
        true length rides along as ``batch["lengths"]`` — causality (and,
        for recurrent families, exact pad-identity masking in the mamba
        scans) keeps real positions unaffected by pad tokens,
        ``lm_prefill`` gathers the logits at the true last position, and
        ``scatter_prefill`` copies only the true prompt's pages (garbage
        keys in the trailing page are masked by ``kv_len`` everywhere; its
        Kg/meta rows are zeroed per the staleness contract). The jit cache
        is therefore keyed on the BUCKET, not the prompt length: O(log
        max_len) programs instead of one per distinct length (the page
        scatter is bucket-keyed too — traced length + padded ids). Any
        pages beyond the prompt (upfront ``reserve`` admission) get zeroed
        Kg/meta rows and kv_len-masked filler K/V; under ``lazy``
        admission growth pages are zeroed at allocation time
        (``pg.reset_kg_rows``).

        Family dispatch happens through ``api.state_view`` (PR 10): the
        view names which prefill-state fields scatter into the page pools
        (skipped entirely for a pages-free family) and which rows seed the
        request's slot in ``slot_state``. Returns (pages, slot_state, fp32
        logits row) — the caller samples."""
        plen = req.prompt_len
        bucket = _bucket_pages(plen, ps)
        fn = self._prefill_jit.get(bucket)
        if fn is None:
            def paged_prefill(params, batch):
                return self.api.prefill(params, batch, cfg=self.cfg,
                                        max_len=bucket * ps,
                                        options=self.options)
            # named, so the device trace shows jit_paged_prefill
            fn = self._prefill_jit[bucket] = jax.jit(paged_prefill)
        toks = np.zeros((1, bucket * ps), np.int32)
        toks[0, :plen] = req.prompt
        logits, cstate = fn(self.params,
                            {"tokens": jnp.asarray(toks),
                             "lengths": jnp.asarray([plen], jnp.int32)})
        view = self.api.state_view(cstate)
        if view.k_cache is not None:
            # traced length + power-of-two-padded ids: the scatter compiles
            # once per (cache bucket, id bucket), not once per prompt length
            pages = pg.scatter_prefill(
                pages, view.k_cache, view.v_cache, view.kg_cache,
                jnp.asarray(plen, jnp.int32), pg.pad_page_ids(req.pages),
                ps, kmin_cache=view.meta_kmin, kmax_cache=view.meta_kmax)
        if slot_state is not None and view.slot is not None:
            slot_state = ss.write_slot(slot_state, view.slot,
                                       jnp.asarray(req.slot))
        with _sync("prefill_logits"):
            return pages, slot_state, np.asarray(logits[0], np.float32)

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
