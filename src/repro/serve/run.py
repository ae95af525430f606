"""One ``DecodeEngine.serve`` call: its state and its phases.

``ServeRun`` owns everything a served run shares between its phases: the
scheduler, host swap space and eviction manager, the device page pools
and per-slot recurrent state, the packed step's host-side columns
(``token_buf``, ``budget_blocks``), the per-request sampling / budget /
index maps, the sparsity sums, the counters and the pending failures.
``serve()`` drives one iteration of the decode loop as

    arrive -> admit -> prepare -> launch -> settle -> finish

and each phase keeps its own ``serve.*`` span (README, "Tracing a served
run"; docs/ARCHITECTURE.md, "The serve loop"). ``launch`` dispatches the
step and returns a ``Flight`` of device handles; ``settle`` replays it
until no row read an evicted page and only then adopts its slot state;
``finish`` makes the step's one blocking pull and applies its tokens.
"""
from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serve import paging as pg
from repro.serve import sampling as smp
from repro.serve import slotstate as ss
from repro.serve.eviction import EvictionConfig, EvictionManager
from repro.serve.offload import (HostSwapSpace, PageEntry, SwapEntry,
                                 SwapError)
from repro.serve.scheduler import Request, Scheduler, pages_needed

# the request ids a result dict reserves for itself
RESERVED_RIDS = ("stats", "logits")

# the budget column's never-binding cap, for slots without an override
_NO_CAP = np.int32(2 ** 30)

# a request's lifecycle stamps, as ``stats["timing_by_rid"]`` reports them
_TIMING_FIELDS = ("submit_step", "admit_step", "first_token_step",
                  "retire_step", "t_submit", "t_admit", "t_first",
                  "t_retire")

# serve()'s decode step takes one packed int32 input per step, one row per
# slot: these leading columns (the budget column only when some request
# sets a budget), then the slot's page-table row
_IN_TOKEN, _IN_CUR_LEN, _IN_ACTIVE, _IN_BUDGET = range(4)
# and returns one packed int32 [n_slots, 2 or 4] array that the host pulls
# once: the greedy token, whether the logits row is finite and, under
# measure_sparsity, the float32 bits of the row's sparsity and selected
# block count
_OUT_NEXT, _OUT_FINITE, _OUT_RHO, _OUT_SEL = range(4)


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


def bucket_pages(prompt_len: int, ps: int) -> int:
    """Prefill bucket of a prompt: its page count rounded up to a power of
    two."""
    return 1 << (-(-prompt_len // ps) - 1).bit_length()


class ServeResult(Dict):
    """rid -> list of generated token ids, plus throughput/stats fields
    under the ``stats`` key (dict access, like GenerationResult)."""
    pass


class Flight(NamedTuple):
    """Device handles of one dispatched step, as the step program returns
    them (pages are donated: the run adopts them at dispatch)."""
    logits: Any
    pages: pg.PagedPages
    slot_state: Any
    aux: Dict[str, Any]
    step_out: Any


class ServeRun:
    """The state and phases of one ``DecodeEngine.serve`` call (see the
    module docstring). Built after ``serve()`` has checked its arguments;
    the keyword arguments are ``serve()``'s own."""

    def __init__(self, eng, requests: Sequence[Dict[str, Any]], *,
                 n_slots: int, num_pages: Optional[int],
                 collect_logits: bool, max_steps: Optional[int],
                 sample_seed: int, admission: str, watermark: int,
                 eviction: Optional[EvictionConfig], swap_config, faults,
                 arrivals, on_token, table_pages: Optional[int]):
        self.eng, self.cfg = eng, eng.cfg
        self.ps = ps = eng.cfg.gate.block_size
        self.n_slots, self.collect_logits = n_slots, collect_logits
        self.faults, self.arrivals = faults, arrivals
        self.reqs: List[Request] = []
        # rid -> sampling params / budget override / registration index
        self.sampling_of, self.budget_of, self.ridx_of = {}, {}, {}
        # rid -> [sparsity sum, selected-block sum, decode steps measured]
        self.sel_sums: Dict[Any, list] = {}
        self.rejected_arrivals = 0
        for rd in requests:
            self._register(rd)
        self.base_key = jax.random.PRNGKey(sample_seed)
        # per-slot selected-block caps: ONLY active when some request sets
        # a "budget" (otherwise no mask exists at all — zero risk of
        # clipping a policy whose list is wider than the config budget).
        # With open-loop arrivals the mask must exist up front: whether a
        # LATER arrival carries a budget override cannot retroactively
        # change the compiled step's signature mid-run
        use_budget = (arrivals is not None
                      or any(b is not None for b in self.budget_of.values()))
        track = eviction is not None
        # one jit per flavor per engine, shared by repeat serve() calls;
        # fetched first: eviction's options validate here
        self.step = eng._paged_step(track, use_budget)
        # leading columns of the packed step input, before the page table
        self.lead = _IN_BUDGET + 1 if use_budget else _IN_BUDGET
        self.budget_blocks = (np.full((n_slots,), _NO_CAP, np.int32)
                              if use_budget else None)

        npt = max([pages_needed(r.prompt_len, r.max_new_tokens, ps)
                   for r in self.reqs]
                  + ([int(table_pages)] if table_pages is not None else []))
        if num_pages is None:
            # enough for every slot to hold a worst-case sequence (+null)
            num_pages = n_slots * npt + 1
        self.num_pages = num_pages
        self.sched = Scheduler(
            n_slots, num_pages, ps, npt, admission=admission,
            watermark=watermark, eviction_enabled=track, faults=faults,
            evict_cb=self._evict if track else None,
            release_filter=self._release_filter if track else None,
            on_token=on_token)
        self.swap = HostSwapSpace(config=swap_config, faults=faults)
        for r in self.reqs:
            self.sched.submit(r)

        # how many layer slices the pools carry is a FAMILY property
        # (transformer: self-attn layers; hybrid: attention units; ssm: 0
        # — zero-size pools), not a params-shape hack
        nl = eng.api.paged_attn_layers(self.cfg)
        ghosts = 0 if not track else (
            eviction.ghost_rows if eviction.ghost_rows is not None
            else n_slots * npt)
        # min/max metadata pools only for the policy that reads them
        # (needs_meta is part of the SelectionPolicy protocol)
        self.pages = pg.init_pages(self.cfg, num_pages, nl,
                                   with_meta=eng.options.policy.needs_meta,
                                   ghost_rows=ghosts,
                                   quantize=eng.options.quantize)
        # per-slot recurrent state: the page pools' lifecycle twin
        # for recurrent families — None (an empty pytree) for pages-only
        # families, so the step jit sees zero extra operands
        self.slot_state = (None if eng.api.init_slot_state is None
                           else eng.api.init_slot_state(self.cfg, n_slots))
        mesh = getattr(eng.shard, "mesh", None)
        if mesh is not None and eng.options.kernel_impl == "sharded":
            # paged x sharded: keep the pools resident head-sharded so the
            # per-step shard_map never reshards pool-sized arrays
            from jax.sharding import NamedSharding
            from repro.distributed.sharding import paged_pool_pspecs
            self.pages = jax.device_put(self.pages, jax.tree.map(
                lambda s: NamedSharding(mesh, s),
                paged_pool_pspecs(self.pages, mesh)))
        self.evmgr = None
        if track:
            self.evmgr = EvictionManager(
                self.sched, self.swap, num_phys=num_pages, ghost_rows=ghosts,
                page_size=ps,
                page_bytes=EvictionManager.page_restore_bytes(self.pages),
                always_first_block=self.cfg.gate.always_first_block,
                config=eviction)
        # the scheduler tracks which freed pages may still hold a previous
        # tenant's gate/meta/scale rows; the run zeroes them on device.
        # Reserve admission never grows: every reuse goes through
        # scatter_prefill (which zeroes those rows itself) — no sweeps
        self.gate_paged = admission == "lazy" and (
            self.pages.kg_pages is not None
            or self.pages.kmin_pages is not None
            or self.pages.k_scale_pages is not None)

        self.token_buf = np.zeros((n_slots,), np.int32)
        self.active_now = self.active_sum = self.active_max = 0
        self.idle_spins = self.n_steps = 0
        self.limit = max_steps if max_steps is not None else sum(
            r.max_new_tokens for r in self.reqs) + len(self.reqs) + 8
        self.stopped = False
        # requests whose swap-out/restore hit a permanent fault inside a
        # scheduler callback (where failing in place would corrupt the
        # preemption bookkeeping) — failed right after the callback chain
        # unwinds, before the next step runs
        self.pending_failures: List[tuple] = []
        self.last_aux = self.last_active = None

    # -- requests -----------------------------------------------------------

    def _register(self, rd: Dict[str, Any]) -> Request:
        """One request dict -> a tracked Request. ALL per-request
        bookkeeping (sampling/budget overrides, the fold_in index that keys
        the stochastic sampling chain, sparsity accumulators) is created
        here, so upfront and mid-decode arrivals share one path;
        registration ORDER fixes the sampling keys, which is deterministic
        for a fixed request list + trace."""
        req = Request(
            rid=rd.get("rid", len(self.reqs)),
            prompt=np.asarray(rd["tokens"], np.int32).reshape(-1),
            max_new_tokens=int(rd["max_new_tokens"]),
            tier=str(rd.get("tier", "default")),
            priority=int(rd.get("priority", 0)),
            admit_reserve=bool(rd.get("reserve", False)))
        self.reqs.append(req)
        self.sampling_of[req.rid] = (rd.get("sampling")
                                     or self.eng.options.sampling)
        self.budget_of[req.rid] = rd.get("budget")
        self.ridx_of[req.rid] = len(self.ridx_of)
        self.sel_sums[req.rid] = [0.0, 0.0, 0]
        return req

    def _slot_cap(self, rid) -> int:
        """A request's selected-block cap: override caps CEIL to blocks (a
        request never gets fewer tokens of attention than it asked for —
        the same rounding as DecodeOptions.max_selected) and are floored
        so the force-selected first/last blocks (which rank ahead of every
        scored block by construction) survive."""
        b = self.budget_of[rid]
        if b is None:
            return int(_NO_CAP)
        gate = self.cfg.gate
        floor = max(1, int(gate.always_first_block)
                    + int(gate.always_last_block))
        return max(floor, -(-int(b) // self.ps))

    def _sample(self, req: Request, row_logits) -> int:
        """Sample one slot's next token with the request's params."""
        params_s = self.sampling_of[req.rid]
        if params_s.greedy:
            return int(np.argmax(row_logits))
        key = jax.random.fold_in(
            jax.random.fold_in(self.base_key, self.ridx_of[req.rid]),
            len(req.out_tokens))
        with _sync("sample"):
            return int(smp.make_sampler(params_s)(
                jnp.asarray(row_logits), key=key))

    # -- failures -----------------------------------------------------------

    def _fail(self, req: Request, reason: str) -> None:
        self.sched.fail(req, reason)
        self.swap.discard(req.rid)

    def _flush_failures(self) -> None:
        while self.pending_failures:
            req, reason = self.pending_failures.pop()
            if req.rid not in self.sched.finished:
                self._fail(req, reason)

    def _tick(self) -> None:
        """Advance the virtual clock one decode-loop iteration. Past the
        step limit, the watchdog fails whatever is unfinished with partial
        results + telemetry instead of raising away the finished requests'
        outputs, and the run stops."""
        self.n_steps += 1
        if self.n_steps > self.limit:
            for r in self.reqs:
                if r.rid not in self.sched.finished:
                    self._fail(r, "step_limit")
            self.stopped = True

    # -- device side of the page lifecycle ------------------------------------

    def _zero_stale(self, ids: List[int]) -> None:
        """Zero the gate/meta/scale rows of recycled pages the scheduler
        drained (the staleness contract needs a ZERO row on every partial
        trailing page)."""
        if ids and self.gate_paged:
            self.pages = pg.reset_kg_rows(self.pages, pg.pad_page_ids(ids))

    def _evict(self, n: int) -> int:
        """Scheduler callback: evict up to ``n`` cold pages."""
        self.pages, freed = self.evmgr.evict(self.pages, n)
        return freed

    def _release_filter(self, req: Request) -> List[int]:
        """Scheduler callback: the physical pages of a request leaving its
        slot (ghost ids of evicted pages are not allocator pages)."""
        # heat rows are per-slot state; the slot is being vacated
        if req.slot >= 0 and self.sched.slots[req.slot] is req:
            self.evmgr.heat.reset_row(req.slot)
        self.evmgr.forget(req)    # drop host entries, reclaim ghosts
        return [p for p in req.pages if p < self.num_pages]

    def swap_out(self, req: Request) -> None:
        """Preemption callback: capture the victim's device pages (and its
        pending token) into host swap space BEFORE they are freed.
        ``req.pages`` is in logical order, so restore is a plain scatter.
        Only CONTENT pages are captured — a growth page allocated for the
        not-yet-written next token is dropped (it is empty; re-admission
        re-grows it), keeping the swap footprint equal to what
        re-admission will allocate.

        Preempt x evict merge: blocks of the victim that page eviction
        already moved to host swap are stitched back into the single
        SwapEntry from their PageEntries (the device ghost rows only mirror
        gate/meta state; K/V truth for an evicted page lives on the host),
        so resume takes the unchanged — bitwise-pinned — whole-request
        restore path. A permanent swap fault here marks the victim failed
        instead of raising through the scheduler.

        Recurrent families: the victim's per-layer recurrent rows
        ride along in the entry (``state_conv``/``state_h``) — captured
        from the PRE-step buffer (the step jit never donates
        ``slot_state``), which together with the pending ``token`` is
        exactly the point decode resumes from."""
        with TraceAnnotation("serve.swap_out", rid=req.rid):
            n_content = max(1, -(-req.swap_len // self.ps))
            # ghost ids carry no K/V — extract through the trash page and
            # overwrite those blocks from their host PageEntries below
            phys_ids = [p if p < self.num_pages else pg.NULL_PAGE
                        for p in req.pages[:n_content]]
            # power-of-two id padding (trash-page ids): bounds the jit
            # cache of extract/restore to O(log pool) programs;
            # re-admission pads the same n_content to the same bucket, so
            # shapes match
            rows = [_pull(a, f"swap_{what}") for a, what in zip(
                pg.extract_pages(self.pages, pg.pad_page_ids(phys_ids)),
                PageEntry._fields)]
            reason = None
            if self.evmgr is not None:
                blocks = self.evmgr.evicted.pop(req.rid, None) or {}
                for lb, ghost in sorted(blocks.items()):
                    self.evmgr.ghost_free.append(ghost)
                    try:
                        pe = self.swap.pop(("page", req.rid, lb))
                    except SwapError:
                        reason = "restore_failed"
                        continue
                    for dst, src in zip(rows, pe):
                        if dst is not None and src is not None:
                            dst[:, lb] = src[:, 0]
            st_conv = st_h = None
            if self.slot_state is not None:
                row = ss.read_slot(self.slot_state, jnp.asarray(req.slot))
                st_conv = _pull(row.conv, "swap_conv")
                st_h = _pull(row.h, "swap_h")
            if reason is None:
                try:
                    self.swap.put(req.rid, SwapEntry(
                        **dict(zip(PageEntry._fields, rows)),
                        token=int(self.token_buf[req.slot]),
                        cur_len=req.swap_len,
                        state_conv=st_conv, state_h=st_h))
                except SwapError:
                    reason = "swap_put_failed"
            if reason is not None:
                self.pending_failures.append((req, reason))

    def prefill(self, req: Request):
        """Contiguous prefill of one request, scattered into its pages.

        Prompt lengths are rounded UP to power-of-two page buckets: tokens
        are right-padded to the bucket width and the
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

        Family dispatch happens through ``api.state_view``: the
        view names which prefill-state fields scatter into the page pools
        (skipped entirely for a pages-free family) and which rows seed the
        request's slot in ``slot_state``. Returns the fp32 logits row — the
        caller samples."""
        plen, ps = req.prompt_len, self.ps
        bucket = bucket_pages(plen, ps)
        toks = np.zeros((1, bucket * ps), np.int32)
        toks[0, :plen] = req.prompt
        logits, cstate = self.eng._prefill_fn(bucket)(
            self.eng.params, {"tokens": jnp.asarray(toks),
                              "lengths": jnp.asarray([plen], jnp.int32)})
        view = self.eng.api.state_view(cstate)
        if view.k_cache is not None:
            # traced length + power-of-two-padded ids: the scatter compiles
            # once per (cache bucket, id bucket), not once per prompt length
            self.pages = pg.scatter_prefill(
                self.pages, view.k_cache, view.v_cache, view.kg_cache,
                jnp.asarray(plen, jnp.int32), pg.pad_page_ids(req.pages),
                ps, kmin_cache=view.meta_kmin, kmax_cache=view.meta_kmax)
        if self.slot_state is not None and view.slot is not None:
            self.slot_state = ss.write_slot(self.slot_state, view.slot,
                                            jnp.asarray(req.slot))
        with _sync("prefill_logits"):
            return np.asarray(logits[0], np.float32)

    def restore(self, req: Request, entry: SwapEntry) -> None:
        """Resume: scatter a swapped-out request's pages (and its recurrent
        rows) back from host swap space into its new pages and slot."""
        self.pages = pg.restore_pages(
            self.pages, pg.to_dev(entry.k), pg.to_dev(entry.v),
            pg.to_dev(entry.kg), pg.pad_page_ids(req.pages),
            pg.to_dev(entry.kmin), pg.to_dev(entry.kmax),
            k_scale=pg.to_dev(entry.k_scale),
            v_scale=pg.to_dev(entry.v_scale))
        if self.slot_state is None or (entry.state_conv is None
                                       and entry.state_h is None):
            return
        row = ss.SlotState(conv=pg.to_dev(entry.state_conv),
                           h=pg.to_dev(entry.state_h))
        self.slot_state = ss.write_slot(self.slot_state, row,
                                        jnp.asarray(req.slot))

    # -- the phases of one decode-loop iteration ------------------------------

    def has_work(self) -> bool:
        return not self.stopped and (
            self.sched.has_work()
            or (self.arrivals is not None and not self.arrivals.exhausted))

    def arrive(self) -> None:
        """Set the scheduler's virtual clock and take this step's open-loop
        arrivals."""
        # lifecycle ``*_step`` stamps and the arrival schedule both read
        # the decode-loop iteration counter, never wall time — fixed trace
        # => fixed schedule
        self.sched.now = self.n_steps
        if self.arrivals is None:
            return
        with TraceAnnotation("serve.arrivals") as span:
            handed = self.arrivals.pull(self.n_steps)
            span.set_metadata(handed=len(handed))
            for rd in handed:
                rid = rd.get("rid", len(self.reqs))
                if rid in self.ridx_of or rid in RESERVED_RIDS:
                    # malformed trace entry: drop it (never-raises — the
                    # already-running batch must not pay for it)
                    self.rejected_arrivals += 1
                    continue
                req = self._register(rd)
                try:
                    self.sched.submit(req)
                except ValueError as e:
                    # an arriving request the pool/table can never hold
                    # fails ALONE with the reason, mid-run
                    self.sched.fail(req, f"submit_rejected: {e}")

    def admit(self) -> None:
        """Admit pending requests into free slots: prefill a fresh one,
        restore a preempted one from host swap space."""
        sched = self.sched
        for req in sched.admissions():
            with TraceAnnotation(
                    "serve.admit", rid=req.rid, prompt_len=req.prompt_len,
                    **({"resumed": True} if req.swapped else
                       {"bucket": bucket_pages(req.prompt_len, self.ps)})):
                if req.swapped:        # resume: restore, don't prefill
                    try:
                        entry = self.swap.pop(req.rid)
                    except SwapError:
                        # permanently unreadable swap entry: the request's
                        # KV is gone — fail IT, keep serving the others
                        self._fail(req, "restore_failed")
                        continue
                    with TraceAnnotation("serve.restore", rid=req.rid):
                        self.restore(req, entry)
                    self.token_buf[req.slot] = entry.token
                    req.swapped = False
                else:
                    lg = self.prefill(req)
                    first = self._sample(req, lg)
                    req.out_tokens.append(first)
                    sched.note_token(req, first)  # TTFT stamp + stream
                    if self.collect_logits:
                        req.out_logits.append(lg)
                    self.token_buf[req.slot] = first
                sched.mark_live(req.pages)             # content written
                if self.budget_blocks is not None:
                    self.budget_blocks[req.slot] = self._slot_cap(req.rid)
                sched.retire_if_done(req)

    def prepare(self) -> None:
        """Page-cap eviction, lazy growth and preemption before the step;
        zero the recycled pages growth just handed out."""
        with TraceAnnotation("serve.prepare"):
            if self.evmgr is not None:
                self.pages = self.evmgr.enforce_caps(self.pages)
            fresh = self.sched.prepare_step(self.swap_out)
            self._flush_failures()
            # growth only re-zeroes a page freed in this same iteration
            # (LIFO reuse); the rest wait for the end-of-step sweep
            self._zero_stale(self.sched.drain_released(fresh))

    def idle(self) -> bool:
        """True when no slot is active after ``prepare``, so there is no
        step to launch this iteration."""
        if self.sched.active.any():
            self.idle_spins = 0
            return False
        if not self.sched.pending:
            if self.arrivals is not None and not self.arrivals.exhausted:
                # open-loop gap: nothing to decode yet but the trace has
                # more arrivals — tick the virtual clock forward so they
                # come due (bounded by max_steps)
                self._tick()
            return True
        # preemption may have just vacated every slot while freeing its
        # pages — loop back through admissions once before declaring a
        # stall
        self.idle_spins += 1
        if self.idle_spins > 1:
            # no-progress watchdog: admission is stuck (e.g. the allocator
            # keeps faulting). Fail the request admission keeps choosing
            # (highest priority, FIFO within the class) — each firing
            # unblocks the queue by one, so the loop always terminates —
            # instead of raising away everyone's partial results.
            self._fail(max(self.sched.pending, key=lambda r: r.priority),
                       "admission_stall")
            self.idle_spins = 0
        return True

    def launch(self) -> Flight:
        """Dispatch the step over every slot; returns its device handles."""
        self.active_now = int(self.sched.active.sum())
        self.active_sum += self.active_now
        self.active_max = max(self.active_max, self.active_now)
        return self._dispatch()

    def _dispatch(self) -> Flight:
        sched = self.sched
        # slot_state is NOT donated and NOT adopted until the step is
        # accepted (``settle``): a faulted attempt is re-run from the SAME
        # recurrent state (updates are not idempotent), which keeps the
        # replay bitwise-equal to a never-faulted step
        with TraceAnnotation("serve.dispatch", active=self.active_now):
            # one upload: a fresh host array every attempt (the device may
            # alias it while the step is in flight)
            step_in = np.empty(
                (self.n_slots, self.lead + sched.page_table.shape[1]),
                np.int32)
            step_in[:, _IN_TOKEN] = self.token_buf
            step_in[:, _IN_CUR_LEN] = sched.cur_len
            step_in[:, _IN_ACTIVE] = sched.active
            if self.budget_blocks is not None:
                step_in[:, _IN_BUDGET] = self.budget_blocks
            step_in[:, self.lead:] = sched.page_table
            flight = Flight(*self.step(self.eng.params, self.pages,
                                       self.slot_state, jnp.asarray(step_in)))
        self.pages = flight.pages       # the old pools were donated
        return flight

    def settle(self, flight: Flight) -> Flight:
        """Replay the step until no active row read an evicted page (or the
        valve fails the thrashing rows); adopt the accepted attempt's slot
        state and return that attempt."""
        sched, evmgr, replays = self.sched, self.evmgr, 0
        while evmgr is not None:
            with _sync("touched_pages"):
                touched = np.asarray(flight.aux["touched_pages"], bool)
            faulted = (touched & (sched.page_table >= self.num_pages)
                       & sched.active[:, None])
            if not faulted.any():
                # victim model feeds on FAULT-FREE steps only (replay reads
                # are restore traffic, not attention heat)
                evmgr.heat.observe(touched, sched.active)
                break
            # optimistic execution faulted: some row selected a block whose
            # K/V is evicted (its gate/meta ghost rows scored it normally).
            # Restore the pages and RE-RUN the step; page writes are
            # idempotent (the trailing append rewrites the same values at
            # the same positions before any read), so the replay is bitwise
            # equal to a never-faulted step.
            evmgr.n_replays += 1
            replays += 1
            faulted_slots = np.nonzero(faulted.any(axis=1))[0]
            if replays > evmgr.config.max_replays:
                # evict/restore thrash: fail the faulted requests. The
                # surviving rows of this run never read a ghost, so their
                # logits are valid as-is.
                for slot in faulted_slots:
                    if sched.slots[slot] is not None:
                        self._fail(sched.slots[slot], "restore_thrash")
                break
            with TraceAnnotation(
                    "serve.replay", replay=replays,
                    rid=[sched.slots[s].rid for s in faulted_slots
                         if sched.slots[s] is not None]):
                # pin every page ANY active row touched (plus trailing):
                # restoring row A must not evict what row B's replay reads,
                # or the replay loop could ping-pong forever
                pinned = set()
                for slot in np.nonzero(sched.active)[0]:
                    r = sched.slots[slot]
                    for lb in np.nonzero(touched[slot])[0]:
                        pinned.add((r.rid, int(lb)))
                    pinned.add((r.rid, int(sched.cur_len[slot]) // self.ps))
                for slot in faulted_slots:
                    r = sched.slots[slot]
                    if r is None or not sched.active[slot]:
                        continue  # preempted restoring another row
                    lbs = [int(x) for x in np.nonzero(faulted[slot])[0]]
                    self.pages, ok = evmgr.restore(
                        self.pages, r, lbs, pinned=pinned,
                        swap_out=self.swap_out)
                    if not ok:
                        self._fail(r, "restore_failed")
                self._flush_failures()
            if not sched.active.any():
                break
            flight = self._dispatch()
        # the attempt that broke the loop is the accepted one (fault-free,
        # or its surviving rows' outputs are valid); slots that
        # failed/retired/preempted get their rows rewritten at the next
        # admission or restore before anything reads them
        self.slot_state = flight.slot_state
        return flight

    def finish(self, flight: Flight) -> None:
        """The step's one blocking pull, failure isolation, sampling, the
        sparsity sums and completion; then tick the clock."""
        sched = self.sched
        if not sched.active.any():
            # every row failed or was preempted mid-replay; count the spin
            # against the step limit so injected-fault storms still
            # terminate
            self._tick()
            return
        self.last_aux = flight.aux
        # idle/retired slots decode garbage rows (rho=0): remember who was
        # live so sparsity_stats() averages ACTIVE rows only
        self.last_active = sched.active.copy()
        slot_reqs = list(sched.slots)   # before retirement mutates it
        # the step's one blocking pull: greedy tokens, finite flags and the
        # sparsity rows, packed on the device
        with _sync("step_out"):
            out_np = np.asarray(flight.step_out)
        # per-request failure isolation: a non-finite logits row (a
        # poisoned request, or an injected "logits" fault) is retired with
        # an error instead of sampling garbage into the batch
        finite = out_np[:, _OUT_FINITE] != 0
        if self.faults is not None and self.faults.fire("logits"):
            act = np.nonzero(sched.active)[0]
            if act.size:
                finite[act[0]] = False
        for slot in np.nonzero((~finite) & sched.active)[0]:
            self._fail(sched.slots[slot], "non_finite_logits")
        # host-side per-slot sampling runs ONLY while a LIVE request is
        # stochastic; otherwise (and again once every stochastic request
        # retires) the device-side batched argmax transfers n_slots ints,
        # not [n_slots, V] logits. The stochastic path pays one tiny
        # dispatch per active slot per step — batching slots that share
        # SamplingParams (vmapped keys) is a serving-scale follow-up.
        stoch = any(not self.sampling_of[slot_reqs[s].rid].greedy
                    for s in np.nonzero(sched.active)[0])
        lg_np = None
        if self.collect_logits or stoch:
            with _sync("logits"):
                lg_np = np.asarray(flight.logits, np.float32)
        if stoch:
            with TraceAnnotation("serve.sample"):
                nxt = np.zeros((self.n_slots,), np.int32)
                for slot in np.nonzero(sched.active)[0]:
                    nxt[slot] = self._sample(slot_reqs[slot], lg_np[slot])
        else:
            nxt = out_np[:, _OUT_NEXT]
        if self.eng.options.measure_sparsity:
            rho_rows = out_np[:, _OUT_RHO].view(np.float32)
            sel_rows = out_np[:, _OUT_SEL].view(np.float32)
            for slot in np.nonzero(sched.active)[0]:
                sums = self.sel_sums[slot_reqs[slot].rid]
                sums[0] += float(rho_rows[slot])
                sums[1] += float(sel_rows[slot])
                sums[2] += 1
        with TraceAnnotation("serve.complete"):
            sched.complete_step(nxt, lg_np if self.collect_logits else None)
            # every page freed this step (retirements included)
            self._zero_stale(sched.drain_released())
            self.token_buf = np.where(sched.active, nxt, 0).astype(np.int32)
        self._tick()

    # -- result -------------------------------------------------------------

    def result(self, wall: float) -> ServeResult:
        sched, swap, evmgr, reqs = self.sched, self.swap, self.evmgr, self.reqs
        out = ServeResult()
        for r in reqs:
            out[r.rid] = r.out_tokens
        if self.collect_logits:
            out["logits"] = {r.rid: np.stack(r.out_logits)
                             for r in reqs if r.out_logits}
        gen_toks = sum(len(r.out_tokens) for r in reqs)
        # slot_util over DECODE-step tokens only (each admission's first
        # token comes from prefill, not from a decode slot)
        decode_toks = gen_toks - sched.n_admitted
        # "retired" counts every finished request; requests that were
        # preempted at least once along the way are broken out separately
        retired_preempted = sum(1 for r in sched.finished.values()
                                if r.n_preemptions > 0)
        n_steps, sums = self.n_steps, self.sel_sums
        out["stats"] = {
            "wall_s": wall, "decode_steps": n_steps,
            "generated_tokens": gen_toks,
            "tok_per_s": gen_toks / max(wall, 1e-9),
            "slot_util": decode_toks / max(n_steps * self.n_slots, 1),
            "admitted": sched.n_admitted, "retired": sched.n_retired,
            "retired_clean": sched.n_retired - retired_preempted,
            "retired_preempted": retired_preempted,
            "admission_stalls": sched.admission_stalls,
            "admission": sched.admission, "watermark": sched.watermark,
            "preemptions": sched.n_preemptions,
            "resumed": sched.n_resumed,
            "swapped_out_bytes": swap.bytes_out,
            "swapped_in_bytes": swap.bytes_in,
            # failure isolation + memory-pressure telemetry
            "failed": sched.n_failed,
            "errors": {r.rid: r.error for r in sched.finished.values()
                       if r.status != "ok"},
            "swap": swap.stats(),
            "faults": None if self.faults is None else self.faults.stats(),
            "evictions": 0 if evmgr is None else evmgr.n_evicted,
            "page_restores": 0 if evmgr is None else evmgr.n_page_restores,
            "replay_steps": 0 if evmgr is None else evmgr.n_replays,
            "mean_active_slots": self.active_sum / max(n_steps, 1),
            "max_active_slots": self.active_max,
            "peak_pages_used": (sched.allocator.num_pages - 1
                                - sched.allocator.min_free),
            "num_pages": self.num_pages, "page_size": self.ps,
            # devices the K/V page pool spans (the kv-head shards of the
            # paged x sharded path; 1 unsharded)
            "pool_devices": len(self.pages.k_pages.sharding.device_set),
            # bucketed-prefill jit cache (bounded: one program per
            # power-of-two page count ever seen by this engine)
            "prefill_jit_programs": len(self.eng._prefill_jit),
            "prefill_buckets_pages": sorted(self.eng._prefill_jit),
            # measured per-request selection telemetry (decode steps only;
            # empty — not zero — when telemetry is compiled out)
            "sparsity_by_rid": {rid: s[0] / s[2]
                                for rid, s in sums.items() if s[2]},
            "sel_blocks_by_rid": {rid: s[1] / s[2]
                                  for rid, s in sums.items() if s[2]},
            # per-request lifecycle (``*_step`` on the virtual clock —
            # deterministic TTFT/TPOT proxies; ``t_*`` wall-clock seconds,
            # -1.0 where the stage was never reached)
            "timing_by_rid": {r.rid: {
                **{f: getattr(r, f) for f in _TIMING_FIELDS},
                "n_tokens": len(r.out_tokens)} for r in reqs},
            "tier_by_rid": {r.rid: r.tier for r in reqs},
            "rejected_arrivals": self.rejected_arrivals,
        }
        return out
