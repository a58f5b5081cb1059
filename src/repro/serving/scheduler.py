"""Host-side continuous-batching scheduler (no device work here).

The scheduler owns every per-request decision the engines make —
admission into free slots, chunked-prefill progress, decode membership,
termination — and hands the engines *fixed-shape* numpy plans to feed
the compiled device steps:

  * ``plan_prefill`` admits queued requests into free slots and returns
    ONE ``(slots, prefill_chunk)`` token block covering every slot that
    still has prompt pieces to prefill — admissions are batched into a
    single prefill call per engine step (the original engine ran one
    full ``slots x prefill_len`` forward *per request* and discarded all
    but one slot's rows), and long prompts advance one ``prefill_chunk``
    piece per step so time-to-first-token stays bounded by the chunk
    compute, not the longest prompt.
  * ``plan_decode`` covers every slot whose prefill completed.

Admission semantics match the original engine exactly (the batched-admit
regression test pins this): prompts are truncated to their *last*
``prefill_len`` tokens, left-padded with zeros, and a slot's cache
length starts at ``prefill_len`` regardless of the true prompt length.
A truncated prompt is now recorded (``Request.truncated``) and rejected
loudly when the scheduler runs in strict mode.

Because both the single-device and the sharded engines drive this same
scheduler, their step sequences — and therefore their sampler key
streams — are identical, which is what makes cross-engine token-parity
testable.

Paged mode (``paging=PageManager(...)``): admission is planned against
the *free-page budget* instead of free slots alone — a request is
admitted when a free slot exists in a group with enough free-or-
evictable pages for its prompt (minus whatever prefix-cache pages it
can reuse). Admission stays arrival-ordered with an explicit
starvation guard: a request that does not fit may be bypassed by later
arrivals **once**; the second time it fails to fit, admission stops
behind it until it gets in. Decode allocates pages lazily (one page
per ``page_size`` generated tokens); when the pool is exhausted the
scheduler preempts the latest-admitted slot in the group
(pages freed, request requeued at the front for recompute).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np

from repro import obs as _obs
from repro.serving.paging import PageManager, PoolExhaustedError, page_keys


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (plen,) int32
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False  # prompt exceeded prefill_len (tail kept)
    bypassed: bool = False   # a later arrival was admitted past this one
    # serving timestamps (perf_counter seconds; engines fill these in)
    t_submit: Optional[float] = None
    t_first: Optional[float] = None   # first token available (TTFT end)
    t_done: Optional[float] = None
    # per-token availability timestamps (one per entry of ``out``) — the
    # per-request source of truth for inter-token latency; cleared on
    # preemption together with ``out`` (the recompute re-emits them)
    t_tokens: list = dataclasses.field(default_factory=list)

    def itl_s(self) -> np.ndarray:
        """This request's inter-token gaps (seconds), possibly empty."""
        if len(self.t_tokens) < 2:
            return np.asarray([], np.float64)
        return np.diff(np.asarray(self.t_tokens, np.float64))


@dataclasses.dataclass
class PrefillPlan:
    tokens: np.ndarray     # (slots, prefill_chunk) int32
    cache_len: np.ndarray  # (slots,) int32 — per-slot write offset
    mask: np.ndarray       # (slots,) bool — slots whose cache rows to keep
    active: list           # slot ids prefilling this step
    finishing: list        # subset completing their final chunk
    real_rows: int = 0     # real prompt tokens among tokens' rows


@dataclasses.dataclass
class DecodePlan:
    tokens: np.ndarray   # (slots, 1) int32 — last sampled token per slot
    lengths: np.ndarray  # (slots,) int32 — current cache lengths
    mask: np.ndarray     # (slots,) bool — slots whose cache rows to keep
    active: list         # slot ids decoding this step
    ctx_tokens: int = 0  # sum of the active slots' cache lengths


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    tokens: Optional[np.ndarray] = None  # padded (prefill_len,) prompt
    pos: int = 0      # prefill progress (tokens written to cache)
    length: int = 0   # decode-time cache length
    # paged-mode bookkeeping
    seq: int = 0                  # admission order (preemption victim pick)
    hit_pages: int = 0            # prefix pages reused at admission
    keys: Optional[list] = None   # per-page chain hashes of the prompt


class Scheduler:
    def __init__(self, *, slots: int, max_seq: int, prefill_len: int,
                 prefill_chunk: Optional[int] = None, strict: bool = False,
                 paging: Optional[PageManager] = None, obs=None):
        self.prefill_chunk = prefill_chunk or prefill_len
        if prefill_len % self.prefill_chunk:
            raise ValueError(
                f"prefill_len={prefill_len} must be a multiple of "
                f"prefill_chunk={self.prefill_chunk} (fixed-shape chunks)")
        self.n_slots = slots
        self.max_seq = max_seq
        self.prefill_len = prefill_len
        self.strict = strict
        self.paging = paging
        if paging is not None:
            if prefill_len % paging.page_size:
                raise ValueError(
                    f"prefill_len={prefill_len} must be a multiple of "
                    f"page_size={paging.page_size}: decode must start on "
                    "a fresh page so prompt pages stay immutable once "
                    "registered in the prefix cache")
            # prefix hits advance in whole pages AND whole chunks
            self._hit_gran = math.lcm(paging.page_size, self.prefill_chunk)
        self.slots = [_Slot() for _ in range(slots)]
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.preemptions = 0
        self._admit_seq = 0
        self.obs = obs if obs is not None else _obs.get_obs()

    # ---- observability ----------------------------------------------------

    def _count(self, name: str, n: float = 1, **labels) -> None:
        if self.obs is not None:
            self.obs.metrics.inc(name, n, **labels)

    def _admitted(self, req: Request, slot: int, group: int,
                  hit_pages: int) -> None:
        """Per-request span begins at admission (readmission after a
        preemption opens a fresh ``b`` under the same request id)."""
        self._count("sched_admissions_total")
        if self.obs is not None:
            self.obs.tracer.async_begin(
                f"request {req.rid}", req.rid, slot=slot, group=group,
                truncated=req.truncated, hit_pages=hit_pages)

    # ---- admission --------------------------------------------------------

    def submit(self, req: Request, now: Optional[float] = None) -> None:
        if len(req.prompt) > self.prefill_len:
            req.truncated = True
            if self.strict:
                raise ValueError(
                    f"request {req.rid}: prompt length {len(req.prompt)} "
                    f"exceeds prefill_len={self.prefill_len} and the "
                    "engine is strict (tail truncation refused)")
        req.t_submit = now
        self.queue.append(req)
        self._count("sched_submitted_total")
        if req.truncated:
            self._count("sched_truncated_total")

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s.req is not None for s in self.slots)

    def _padded(self, prompt: np.ndarray) -> np.ndarray:
        p = np.asarray(prompt, np.int32)[-self.prefill_len:]
        tok = np.zeros(self.prefill_len, np.int32)
        tok[self.prefill_len - len(p):] = p
        return tok

    # ---- paged admission --------------------------------------------------

    def _plan_hit(self, group: int, keys: list) -> tuple[int, list]:
        """Longest page-and-chunk-aligned cached prefix of ``keys``.

        Capped at ``prefill_len - prefill_chunk``: the final chunk always
        runs so the finishing step has last-token logits to sample from.
        Returns (hit_pages, gids) — not yet retained."""
        pm = self.paging
        gids = []
        for k in keys:
            gid = pm.peek(group, k)
            if gid is None:
                break
            gids.append(gid)
        hit_tokens = min(len(gids) * pm.page_size,
                         self.prefill_len - self.prefill_chunk)
        hit_tokens -= hit_tokens % self._hit_gran
        hit_pages = hit_tokens // pm.page_size
        return hit_pages, gids[:hit_pages]

    def _admit_paged(self) -> None:
        """Arrival-ordered admission against the page budget.

        Starvation guard: the first time a request doesn't fit it is
        marked ``bypassed`` and later arrivals may still be admitted
        past it; the second time, admission stops at it — no request is
        ever passed over twice while pages/slots free up behind it."""
        pm = self.paging
        free = [i for i, s in enumerate(self.slots) if s.req is None]
        pages_per_prompt = self.prefill_len // pm.page_size
        for req in list(self.queue):
            if not free:
                break
            tokens = self._padded(req.prompt)
            keys = page_keys(tokens, pm.page_size)
            placed = None
            for i in free:
                g = pm.slot_group(i)
                hit_pages, gids = self._plan_hit(g, keys)
                need = pages_per_prompt - hit_pages
                if pm.available_pages(g, exclude=gids) >= need:
                    placed = (i, g, hit_pages, gids)
                    break
            if placed is None:
                if req.bypassed:
                    break  # guard: it will be next, or nothing moves
                req.bypassed = True
                self._count("sched_bypasses_total")
                if self.obs is not None:
                    self.obs.tracer.instant("sched.bypass", rid=req.rid)
                continue
            i, g, hit_pages, gids = placed
            free.remove(i)
            self.queue.remove(req)
            self._admit_seq += 1
            slot = self.slots[i]
            slot.req = req
            slot.tokens = tokens
            slot.pos = hit_pages * pm.page_size  # skip cached prefix
            slot.length = 0
            slot.seq = self._admit_seq
            slot.hit_pages = hit_pages
            slot.keys = keys
            req.bypassed = False
            self._admitted(req, i, g, hit_pages)
            pm.count_prefix_lookup(pages_per_prompt)
            for p, gid in enumerate(gids):
                pm.hit(gid)
                pm.assign(i, p, gid)
            for p in range(hit_pages, pages_per_prompt):
                pm.assign(i, p, pm.alloc_or_evict(g))

    def _preempt(self, victim: int) -> None:
        """Evict a running request for recompute: free its pages, clear
        generated output, requeue at the FRONT (it keeps arrival
        priority and the starvation guard protects its readmission)."""
        slot = self.slots[victim]
        req = slot.req
        self.paging.free_slot(victim)
        req.out.clear()
        req.t_tokens.clear()
        req.t_first = None
        req.bypassed = False
        self.queue.insert(0, req)
        self.slots[victim] = _Slot()
        self.preemptions += 1
        self._count("sched_preemptions_total")
        if self.obs is not None:
            self.obs.tracer.async_end(f"request {req.rid}", req.rid,
                                      preempted=True)

    def _ensure_decode_page(self, i: int) -> bool:
        """Make sure slot i's next decode write lands in an owned page,
        preempting the youngest other slot in the group if the pool is
        exhausted. False = slot i itself got unschedulable (cannot
        happen while i holds pages; defensive)."""
        pm = self.paging
        p = self.slots[i].length // pm.page_size
        if pm.table[i, p] != 0:
            return True
        g = pm.slot_group(i)
        while True:
            try:
                pm.assign(i, p, pm.alloc_or_evict(g))
                return True
            except PoolExhaustedError:
                victims = [j for j, s in enumerate(self.slots)
                           if j != i and s.req is not None
                           and pm.slot_group(j) == g]
                if not victims:
                    raise PoolExhaustedError(
                        f"slot {i} needs a decode page but group {g} has "
                        "no free, evictable, or preemptible page — pool "
                        "too small for a single max-length request"
                    ) from None
                self._preempt(max(victims, key=lambda j: self.slots[j].seq))

    # ---- prefill ----------------------------------------------------------

    def plan_prefill(self) -> Optional[PrefillPlan]:
        if self.paging is not None:
            self._admit_paged()
        else:
            for i, slot in enumerate(self.slots):
                if slot.req is None and self.queue:
                    slot.req = self.queue.pop(0)
                    slot.tokens = self._padded(slot.req.prompt)
                    slot.pos = 0
                    slot.length = 0
                    self._admitted(slot.req, i, 0, 0)
        chunk = self.prefill_chunk
        active, finishing = [], []
        real_rows = 0
        tokens = np.zeros((self.n_slots, chunk), np.int32)
        cache_len = np.zeros(self.n_slots, np.int32)
        mask = np.zeros(self.n_slots, bool)
        for i, slot in enumerate(self.slots):
            if slot.req is None or slot.pos >= self.prefill_len:
                continue
            tokens[i] = slot.tokens[slot.pos:slot.pos + chunk]
            cache_len[i] = slot.pos
            mask[i] = True
            active.append(i)
            # the prompt (its last prefill_len tokens) sits right-aligned
            # in the padded row: count its tokens inside this chunk
            first = self.prefill_len - min(len(slot.req.prompt),
                                           self.prefill_len)
            real_rows += max(0, min(slot.pos + chunk, self.prefill_len)
                             - max(slot.pos, first))
            if slot.pos + chunk >= self.prefill_len:
                finishing.append(i)
        if not active:
            return None
        return PrefillPlan(tokens, cache_len, mask, active, finishing,
                           real_rows)

    def finish_prefill(self, plan: PrefillPlan, sampled: np.ndarray,
                       now: Optional[float] = None) -> None:
        """Advance chunk progress; record the first sampled token for
        slots whose prompt is now fully prefilled."""
        for i in plan.active:
            slot = self.slots[i]
            slot.pos += self.prefill_chunk
            if self.obs is not None and i not in plan.finishing:
                self.obs.tracer.async_instant(
                    "prefill_chunk", slot.req.rid, slot=i,
                    pos=slot.pos, of=self.prefill_len)
        for i in plan.finishing:
            slot = self.slots[i]
            req = slot.req
            req.out.append(int(sampled[i]))
            if now is not None:
                req.t_tokens.append(now)
            if req.t_first is None:
                req.t_first = now
                if self.obs is not None:
                    self.obs.tracer.async_instant("first_token", req.rid,
                                                  slot=i)
                    if req.t_submit is not None and now is not None:
                        self.obs.metrics.observe("serve_ttft_seconds",
                                                 now - req.t_submit)
            slot.length = self.prefill_len
            if self.paging is not None:
                # prompt fully written: publish the owned (non-hit) pages
                # under their chain keys; decode starts on a fresh page
                # (prefill_len % page_size == 0), so these stay immutable
                pm = self.paging
                g = pm.slot_group(i)
                for p in range(slot.hit_pages,
                               self.prefill_len // pm.page_size):
                    pm.register_prefix(g, slot.keys[p], int(pm.table[i, p]))
            if len(req.out) >= req.max_new:
                self._finish(i, now)

    # ---- decode -----------------------------------------------------------

    def plan_decode(self) -> Optional[DecodePlan]:
        if self.paging is not None:
            # lazy page allocation for this round's writes, BEFORE the
            # plan is built: a preempted victim drops out of the round
            for i, slot in enumerate(self.slots):
                if slot.req is not None and slot.pos >= self.prefill_len:
                    self._ensure_decode_page(i)
        tokens = np.zeros((self.n_slots, 1), np.int32)
        lengths = np.zeros(self.n_slots, np.int32)
        mask = np.zeros(self.n_slots, bool)
        active = []
        ctx_tokens = 0
        for i, slot in enumerate(self.slots):
            if slot.req is None or slot.pos < self.prefill_len:
                continue
            tokens[i, 0] = slot.req.out[-1]
            lengths[i] = slot.length
            mask[i] = True
            active.append(i)
            ctx_tokens += slot.length
        if not active:
            return None
        # mask gates the cache merge: a decode call must not write its
        # placeholder token-0 K/V into slots that are mid-chunked-prefill
        # (or empty) — their rows keep the pre-step cache
        return DecodePlan(tokens, lengths, mask, active, ctx_tokens)

    def finish_decode(self, plan: DecodePlan, sampled: np.ndarray,
                      now: Optional[float] = None) -> None:
        for i in plan.active:
            slot = self.slots[i]
            req = slot.req
            req.out.append(int(sampled[i]))
            if now is not None:
                if self.obs is not None and req.t_tokens:
                    self.obs.metrics.observe("serve_itl_seconds",
                                             now - req.t_tokens[-1])
                req.t_tokens.append(now)
            slot.length += 1
            if len(req.out) >= req.max_new or \
                    slot.length >= self.max_seq - 1:
                self._finish(i, now)

    def _finish(self, i: int, now: Optional[float]) -> None:
        slot = self.slots[i]
        req = slot.req
        req.done = True
        req.t_done = now
        self.finished.append(req)
        if self.paging is not None:
            self.paging.free_slot(i)  # pages recycle, not the whole slot
        self.slots[i] = _Slot()
        self._count("sched_finished_total")
        if self.obs is not None:
            self.obs.tracer.async_end(f"request {req.rid}", req.rid,
                                      tokens=len(req.out))
