"""RaaS-style page eviction for the paged serving engine (ISSUE 7).

Page-granular graceful degradation under memory pressure: instead of
swapping out a WHOLE request when the pool runs dry (PR-4 preemption),
evict its coldest FULL pages to the host swap space and keep decoding.
Victim selection follows RaaS (arXiv 2502.11147): per-(slot, block)
attention recency/mass tracked host-side in a ``BlockHeat`` twin of the
selection-metadata cache, fed by the ``touched_pages`` telemetry the
decode step emits under ``DecodeOptions.track_evictions``.

The mechanism that keeps SELECTION bitwise-identical is the ghost row:
the gate (kg) and min/max metadata pools carry ``ghost_rows`` extra page
rows beyond the physical pool. Evicting page ``p`` of logical block
``lb``:

  1. extracts its K/V (and gate/meta, for the swap record) to a host
     ``PageEntry`` keyed ``("page", rid, lb)``,
  2. copies the gate/meta rows ``p -> ghost`` on device
     (``copy_gate_rows``),
  3. points the page table at the ghost id (``>= num_pages``) and frees
     the physical page.

Selection (gate scores, Quest min-max) reads through the RAW page table,
so an evicted block keeps scoring exactly as before. Only the K/V pools
lack ghost rows — attention consumers read through a clamped table
(``min(table, P-1)``), so a step that SELECTS an evicted block reads
garbage K/V. That is detected, never served: the step also reports which
pages each row touched; touched ghost entries are faults, the pages are
restored to fresh physical ids and the step is RE-RUN (optimistic
execution + replay). Page writes are idempotent across replays — the
trailing append/finalize rewrites the same values at the same positions
before any read — so the replay is bitwise equal to a run that never
faulted.

Eligibility guards keep the common case fault-free: never evict the
trailing (partial or force-selected last) block, never block 0 when the
gate force-selects it, never a page touched by the immediately preceding
step, and never a page pinned by the current replay.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from repro.core.metacache import BlockHeat
from repro.serve import paging as pg
from repro.serve.offload import PCIE_BW, HostSwapSpace, PageEntry, SwapError
from repro.serve.scheduler import Request, Scheduler


@dataclasses.dataclass(frozen=True)
class EvictionConfig:
    """Knobs for RaaS page eviction (``DecodeEngine.serve(eviction=...)``).

    max_resident_pages — per-request cap on PHYSICAL pages; the request's
    own coldest eligible pages are evicted before each step to enforce it
    (best-effort: pinned/hot pages can keep it above the cap). None = no
    cap. ema_decay — attention-mass EMA decay per step (RaaS recency
    weighting). max_replays — valve on the optimistic-execution replay
    loop per step; exceeding it fails the thrashing request
    ("restore_thrash") instead of looping forever. ghost_rows — gate/meta
    ghost rows to reserve; None sizes it to one full worst-case sequence
    per slot (every page of every slot evictable at once).
    """
    max_resident_pages: Optional[int] = None
    ema_decay: float = 0.8
    max_replays: int = 8
    ghost_rows: Optional[int] = None


class EvictionManager:
    """Host-side eviction bookkeeping for one ``serve()`` call.

    Owns the ghost-row free list, the rid -> {logical block -> ghost id}
    map of evicted pages, and the ``BlockHeat`` victim model. All device
    work goes through the jitted paging helpers; ``pages`` pytrees are
    threaded through and returned (donated buffers).
    """

    def __init__(self, sched: Scheduler, swap: HostSwapSpace, *,
                 num_phys: int, ghost_rows: int, page_size: int,
                 page_bytes: int, always_first_block: bool,
                 config: EvictionConfig):
        self.sched = sched
        self.swap = swap
        self.P = num_phys                  # table ids >= P are ghosts
        self.page_size = page_size
        # cost-of-restore victim model: score = EMA attention mass x the
        # PCIe restore cost. ``page_bytes`` must be the victim page's
        # ACTUAL restore traffic (``page_restore_bytes`` — K/V page bytes
        # at the pool dtype plus every per-page metadata row that rides
        # the PageEntry), not an fp-assumed constant: int8 pools (ISSUE 9)
        # restore ~4x cheaper and their pages should lose eviction ties
        # against costlier fp tiers accordingly. With uniform page
        # geometry within one pool the cost term is constant, so ordering
        # degenerates to coldest-first.
        self.restore_cost_s = page_bytes / PCIE_BW
        self.always_first_block = always_first_block
        self.config = config
        self.heat = BlockHeat(sched.n_slots, sched.max_pages_per_seq,
                              decay=config.ema_decay)
        self.ghost_free: List[int] = list(range(num_phys,
                                                num_phys + ghost_rows))
        self.evicted: Dict[int, Dict[int, int]] = {}   # rid -> lb -> ghost
        self.n_evicted = 0
        self.n_page_restores = 0
        self.n_replays = 0

    @staticmethod
    def page_restore_bytes(pages: pg.PagedPages) -> int:
        """Bytes that cross PCIe to restore ONE evicted page: its K/V page
        contents at the pool's ACTUAL dtype plus every per-page metadata
        row that rides the ``PageEntry`` (kg, kmin/kmax, int8 quant
        scales). Each pool is ``[L, P, ...]`` with the page id on axis 1,
        so one page's cut across all layers is ``nbytes // P`` — pools
        with ghost rows (kg/kmin/kmax) divide by their own extended row
        count, which is exactly the per-row byte size. This replaces the
        old fp-assumed ``(k+v)//num_pages`` constant: int8 pools
        (ISSUE 9) are ~4x cheaper to restore and the victim model's cost
        term must reflect that."""
        return sum(pool.nbytes // pool.shape[1] for pool in pages
                   if pool is not None)

    # -- victim model -------------------------------------------------------

    def _eligible(self, pinned: Set[Tuple[int, int]],
                  only: Optional[Request] = None
                  ) -> List[Tuple[int, Request, int]]:
        """(slot, req, logical block) triples safe to evict: resident,
        FULL, non-trailing (the trailing block is partial or
        force-selected last), not block 0 under always_first_block, not
        touched by the immediately preceding step, not pinned by the
        current replay."""
        out: List[Tuple[int, Request, int]] = []
        for slot in range(self.sched.n_slots):
            req = self.sched.slots[slot]
            if req is None or not self.sched.active[slot]:
                continue
            if only is not None and req is not only:
                continue
            trailing = int(self.sched.cur_len[slot]) // self.page_size
            start = 1 if self.always_first_block else 0
            for lb in range(start, min(len(req.pages), trailing)):
                if req.pages[lb] >= self.P:
                    continue               # already a ghost
                if (req.rid, lb) in pinned:
                    continue
                if self.heat.last_touch[slot, lb] >= self.heat.step:
                    continue               # read by the last step — hot
                out.append((slot, req, lb))
        return out

    def pick_victims(self, n: int, pinned: Set[Tuple[int, int]] = frozenset(),
                     only: Optional[Request] = None
                     ) -> List[Tuple[Request, int]]:
        """Lowest tier priority first (ISSUE 8: a latency-tier request
        never loses pages while a throughput-tier page is evictable),
        then coldest by score = EMA mass x restore cost; ties break
        (EMA, last_touch, slot, lb) ascending — fully deterministic."""
        cands = self._eligible(pinned, only)
        cands.sort(key=lambda t: (
            t[1].priority,
            float(self.heat.ema[t[0], t[2]]) * self.restore_cost_s,
            float(self.heat.ema[t[0], t[2]]),
            int(self.heat.last_touch[t[0], t[2]]), t[0], t[2]))
        return [(req, lb) for _, req, lb in cands[:n]]

    # -- evict / restore ----------------------------------------------------

    def evict(self, pages: pg.PagedPages, n: int,
              pinned: Set[Tuple[int, int]] = frozenset(),
              only: Optional[Request] = None
              ) -> Tuple[pg.PagedPages, int]:
        """Evict up to ``n`` victim pages; returns (pages, pages freed).

        A victim whose swap put fails (capacity/IO fault) is skipped —
        eviction degrades to freeing fewer pages, and the caller falls
        back to preemption. Freed physical ids go through the scheduler's
        recycled set so their stale gate rows are zeroed before reuse.
        """
        freed = 0
        for req, lb in self.pick_victims(n, pinned, only):
            if not self.ghost_free:
                break
            phys = req.pages[lb]
            entry = PageEntry(*(
                None if a is None else np.asarray(a[:, :1])
                for a in pg.extract_pages(pages, pg.pad_page_ids([phys]))))
            try:
                self.swap.put(("page", req.rid, lb), entry)
            except SwapError:
                continue                   # swap tier full/faulted: skip
            ghost = self.ghost_free.pop()
            pages = pg.copy_gate_rows(pages, pg.pad_page_ids([phys]),
                                      pg.pad_page_ids([ghost]))
            req.pages[lb] = ghost
            self.sched.page_table[req.slot, lb] = ghost
            self.evicted.setdefault(req.rid, {})[lb] = ghost
            self.sched.free_pages([phys])
            self.n_evicted += 1
            freed += 1
        return pages, freed

    def restore(self, pages: pg.PagedPages, req: Request,
                lbs: Sequence[int], *, pinned: Set[Tuple[int, int]],
                swap_out) -> Tuple[pg.PagedPages, bool]:
        """Restore evicted logical blocks of ``req`` to fresh physical
        pages (replay path). Returns (pages, ok); ok=False means a page
        could not come back — no free page even after evicting/preempting
        others, or its swap entry is permanently unreadable — and the
        caller must fail THIS request (failure isolation), not the batch.
        """
        for lb in sorted(lbs):
            ghost = self.evicted.get(req.rid, {}).get(lb)
            if ghost is None:
                continue                   # raced: already restored
            pages, phys = self._acquire(pages, pinned, req, swap_out)
            if phys is None:
                return pages, False
            try:
                pe = self.swap.pop(("page", req.rid, lb))
            except SwapError:
                self.sched.free_pages([phys])
                return pages, False
            pages = pg.restore_pages(
                pages, pg.to_dev(pe.k), pg.to_dev(pe.v), pg.to_dev(pe.kg),
                pg.pad_page_ids([phys]), pg.to_dev(pe.kmin),
                pg.to_dev(pe.kmax), k_scale=pg.to_dev(pe.k_scale),
                v_scale=pg.to_dev(pe.v_scale))
            req.pages[lb] = phys
            self.sched.page_table[req.slot, lb] = phys
            del self.evicted[req.rid][lb]
            if not self.evicted[req.rid]:
                del self.evicted[req.rid]
            self.ghost_free.append(ghost)
            # restore_pages just rewrote this page's gate rows — pull it
            # out of the recycled set or a sweep would zero them
            self.sched.mark_live([phys])
            self.n_page_restores += 1
        return pages, True

    def _acquire(self, pages: pg.PagedPages, pinned: Set[Tuple[int, int]],
                 exclude: Request, swap_out
                 ) -> Tuple[pg.PagedPages, Optional[int]]:
        """One physical page for a restore: alloc -> evict a colder page
        -> preempt a whole other request -> give up (None)."""
        while True:
            ids = self.sched._alloc(1)
            if ids is not None:
                return pages, ids[0]
            pages, freed = self.evict(pages, 1, pinned)
            if freed:
                continue
            victim = self.sched._pick_victim(exclude=exclude)
            if victim is None:
                return pages, None
            self.sched._preempt(victim, swap_out)

    def enforce_caps(self, pages: pg.PagedPages) -> pg.PagedPages:
        """Pre-step per-request resident-page cap (best-effort)."""
        cap = self.config.max_resident_pages
        if cap is None:
            return pages
        for slot in range(self.sched.n_slots):
            req = self.sched.slots[slot]
            if req is None or not self.sched.active[slot]:
                continue
            resident = sum(1 for p in req.pages if p < self.P)
            if resident > cap:
                pages, _ = self.evict(pages, resident - cap, only=req)
        return pages

    # -- lifecycle ----------------------------------------------------------

    def forget(self, req: Request) -> None:
        """Drop every evicted-page record of ``req`` (retire / fail /
        preempt-merge) and hand its ghost ids back to the free list.
        Idempotent."""
        for lb, ghost in self.evicted.pop(req.rid, {}).items():
            self.swap.discard(("page", req.rid, lb))
            self.ghost_free.append(ghost)
