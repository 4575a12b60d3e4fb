"""SLO-aware request scheduling over the serving engines.

``Scheduler`` (PR 5) replaces the thin FIFO admission queue with a
deadline/priority-aware policy built on the freeze machinery's cheapest
primitive: suspending a lane.  Requests carry a strict ``priority`` class
(0 = most important) and optionally a ``deadline_ms`` or an
``slo_tokens_per_s`` decode-rate SLO (converted to a completion
deadline).  The pending queue is a priority heap ordered **strictly
across classes and earliest-deadline-first (EDF) within a class**, with
submission order as the final tie-break — so a trace with no priorities
and no deadlines degrades to exactly the old FIFO behaviour.

**Freeze-native preemption.**  When the best pending request would miss
its deadline waiting for a lane to free naturally, and a strictly
lower-priority request is running, the scheduler preempts.  On the paged
engine it uses install-time preemption (``engine.admit_over``): the
preemptor's chunked prefill runs in scratch while the victim keeps
decoding, and only at install is the victim suspended — its entire
device residency force-stashes to the host store in one batched
transfer, and the continuation is *token-identical* on resume.  The
contiguous engine (and resuming a snapshot, whose pool slice must push
back into a free lane) falls back to immediate ``suspend_lane``;
contiguous resume re-prefills prompt + generated tokens from the
snapshot.  Either way the victim's ``LaneSnapshot`` re-enters the queue
under its own priority/deadline and original submission order, resuming
when capacity returns.  Suspending a lane is nearly free precisely
because the paged engine already treats "this KV lives on the host right
now" as a normal state of the world (ARKV's memory-budget framing;
FreeKV-style retrieval-on-demand makes policy on top of it cheap).

The miss prediction is deliberately simple: an EMA of observed engine
step time, the shortest remaining work across running lanes as the
time-to-free estimate, and chunk-count + decode-length as the service
estimate.  It only gates *when* a preemption fires; correctness never
depends on it.  A second model gates whether preempting is *worth it*:
EMAs of the measured suspend and resume wall cost (``preempt_cost_s``)
veto preemptions whose overhead would eat the whole queue-wait saving.

**Multi-tenancy (PR 10).**  With a ``TenancyController``
(serving/tenancy.py) attached, admission enforces per-tenant quotas
(concurrent-lane caps, token-rate buckets) and weighted fair sharing:
within a priority class the backlogged tenant with the smallest WFQ
virtual time is admitted first, and every committed decode token
advances its tenant's vtime by ``1/weight``.  ``cancel`` / ``pause`` /
``release`` are the server front end's hooks — client disconnects and
per-connection backpressure both route into the freeze-native
suspend/drop machinery rather than growing new engine surface.

Both engines default to the async DMA pipeline (serving/dma.py): a
request may retire one ``step_once`` call after its final token was
computed — the admit-on-free loop is agnostic to that lag, and
``suspend_lane`` flushes the ring first, so preemption decisions act on
committed state.

``StaticScheduler`` keeps the pre-continuous-batching (pre-PR-1)
fixed-batch FIFO behaviour — pad a batch, run everyone for max(n_tokens)
steps, only then admit more — as the comparison baseline for
``benchmarks/continuous_batching.py``.
"""
from __future__ import annotations

import heapq
import math
import time
from typing import Any, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import (ContinuousEngine, Engine, LaneSnapshot,
                                  PagedContinuousEngine, Request,
                                  RequestStatus)
from repro.serving.sampling import SamplingParams
from repro.serving.tenancy import TenancyController

_INF = float("inf")


class Scheduler:
    """Deadline/priority-aware admission (strict classes, EDF within a
    class) with freeze-native lane preemption, over a continuous-batching
    engine (contiguous or paged — both expose the same
    admit/step_once/suspend_lane/resume_lane lane lifecycle).

    ``policy="fifo"`` ignores priorities and deadlines entirely (pure
    submission order, no preemption) — the pre-PR-5 behaviour, kept as
    the benchmark baseline.  ``clock`` is injectable for deterministic
    tests; it must be monotone seconds.

    ``aging_s`` bounds starvation across the strict classes: a queued
    request's *effective* class drops by one (toward 0 = most important)
    for every ``aging_s`` seconds it has waited, so a background request
    under a permanent foreground flood (or a router throttle) is
    eventually admitted instead of starving forever.  Running lanes keep
    their raw class — aging changes who is admitted next, never who is
    preempted."""

    def __init__(self,
                 engine: Union[Engine, ContinuousEngine,
                               PagedContinuousEngine],
                 batch_size: Optional[int] = None, pad_id: int = 0,
                 policy: str = "slo",
                 preemption: bool = True,
                 aging_s: Optional[float] = None,
                 tenancy: Optional[TenancyController] = None,
                 clock=time.monotonic, **kw):
        if isinstance(engine, (ContinuousEngine, PagedContinuousEngine)):
            self.engine = engine
        else:
            self.engine = ContinuousEngine.from_engine(
                engine, n_lanes=batch_size or 1, pad_id=pad_id, **kw)
        assert policy in ("slo", "fifo"), policy
        self.policy = policy
        self.preemption = preemption and policy == "slo"
        self.aging_s = aging_s if policy == "slo" else None
        self.clock = clock
        # heap of (priority, deadline_t, seq, item); item is a Request or
        # a LaneSnapshot (a preempted victim awaiting resume).  Under
        # policy="fifo" the first two components are constants, reducing
        # the order to the seq counter — plain submission order.
        self.queue: List[tuple] = []
        self._seq = 0
        self.done: Dict[int, Request] = {}
        self._uid = 0
        # per-uid SLO bookkeeping (wall times are scheduler-relative)
        self.metrics: Dict[int, Dict[str, Any]] = {}
        self.n_preemptions = 0
        self.n_cancelled = 0
        self._step_s: Optional[float] = None   # EMA of engine step time
        # multi-tenant quotas + weighted fair sharing (serving/tenancy.py);
        # None keeps the single-tenant behaviour bit-for-bit.  A router
        # passes ONE shared controller to every replica via sched_kw.
        self.tenancy = tenancy
        # preemption cost model (the ROADMAP's missing piece): EMAs of the
        # measured wall cost of a suspend and of a resume.  Until BOTH
        # have been observed, preempt_cost_s() reports 0.0 — the first
        # preemption always proceeds and seeds the calibration.
        self._suspend_s: Optional[float] = None
        self._resume_s: Optional[float] = None
        self.n_preempt_skipped_cost = 0

    # ---------------- queue plumbing ---------------- #
    def _deadline_t(self, uid: int) -> Optional[float]:
        return self.metrics[uid]["deadline_t"]

    def _eff_priority(self, req: Request) -> int:
        """The request's class as admission ordering sees it: raw class
        minus one per ``aging_s`` seconds waited (floored at 0)."""
        if self.aging_s is None:
            return req.priority
        waited = self.clock() - self.metrics[req.uid]["arrival_t"]
        return max(0, req.priority - int(waited / self.aging_s))

    def _apply_aging(self) -> None:
        """Re-heap the queue when waiting has promoted any entry's
        effective class — heap keys are computed at push time, so a
        promotion invalidates the stored order.  O(n log n) only on the
        passes where a promotion actually crossed an ``aging_s``
        boundary; a no-op scan otherwise."""
        if self.aging_s is None or not self.queue:
            return
        for key0, _, _, item in self.queue:
            req = item.req if isinstance(item, LaneSnapshot) else item
            if self._eff_priority(req) != key0:
                items = [e[-1] for e in self.queue]
                self.queue = []
                for it in items:
                    self._push(it)
                return

    def _push(self, item: Union[Request, LaneSnapshot]) -> None:
        # the tie-break is the request's ORIGINAL submission seq, not a
        # fresh counter: a preempted victim re-enters the queue ahead of
        # the same-class work submitted after it, so preemption never
        # demotes a request within its class.  (Besides fairness this is
        # what keeps preemption throughput-neutral: victims resume the
        # moment the preemptor retires, instead of their remainders
        # serializing behind the whole class queue at the end of the
        # trace.)  A uid is queued at most once, so seq stays unique.
        req = item.req if isinstance(item, LaneSnapshot) else item
        if self.policy == "fifo":
            key = (0, _INF)
        else:
            dl = self._deadline_t(req.uid)
            key = (self._eff_priority(req), _INF if dl is None else dl)
        heapq.heappush(self.queue,
                       (*key, self.metrics[req.uid]["seq"], item))

    def _peek(self) -> Optional[Union[Request, LaneSnapshot]]:
        return self.queue[0][-1] if self.queue else None

    def _pop(self) -> Union[Request, LaneSnapshot]:
        return heapq.heappop(self.queue)[-1]

    def _pop_admissible(self) -> Optional[Union[Request, LaneSnapshot]]:
        """Pop the next item admission should take.  Without a tenancy
        controller this is the plain heap head.  With one, entries of
        quota-blocked tenants (lane cap reached, token bucket empty) are
        passed over, and WITHIN a priority class the backlogged tenant
        with the smallest WFQ virtual time goes first.  vtime moves with
        every committed token, so the fair-share ordering is computed at
        pop time over a linear scan — the heap keys keep providing the
        class/EDF/seq order for the tenancy-free path and the
        tie-breaks.  Returns None when nothing is quota-admissible."""
        if not self.queue:
            return None
        if self.tenancy is None:
            return heapq.heappop(self.queue)[-1]
        adm: Dict[Optional[str], bool] = {}
        best_i, best_key = None, None
        for i, (p, dl, seq, item) in enumerate(self.queue):
            req = item.req if isinstance(item, LaneSnapshot) else item
            ok = adm.get(req.tenant)
            if ok is None:
                ok = adm[req.tenant] = self.tenancy.may_admit(req.tenant)
            if not ok:
                continue
            key = (p, self.tenancy.vtime(req.tenant), dl, seq)
            if best_key is None or key < best_key:
                best_i, best_key = i, key
        if best_i is None:
            return None
        item = self.queue.pop(best_i)[-1]
        heapq.heapify(self.queue)
        return item

    def submit(self, prompt: np.ndarray, n_tokens: int,
               sampling: SamplingParams = SamplingParams(),
               priority: int = 0,
               deadline_ms: Optional[float] = None,
               slo_tokens_per_s: Optional[float] = None,
               tenant: Optional[str] = None) -> int:
        self._uid += 1
        req = Request(self._uid, np.asarray(prompt, np.int32), n_tokens,
                      sampling, priority=priority, deadline_ms=deadline_ms,
                      slo_tokens_per_s=slo_tokens_per_s, tenant=tenant)
        now = self.clock()
        deadlines = []
        if deadline_ms is not None:
            deadlines.append(now + deadline_ms / 1e3)
        if slo_tokens_per_s:
            deadlines.append(now + n_tokens / slo_tokens_per_s)
        self._seq += 1
        self.metrics[self._uid] = {
            "arrival_t": now, "priority": priority, "seq": self._seq,
            "deadline_t": min(deadlines) if deadlines else None,
            "finish_t": None, "deadline_hit": None, "preempted": 0,
            "shed": 0, "tenant": tenant,
        }
        if self.tenancy is not None:
            self.tenancy.note_enqueue(tenant)
        self._push(req)
        return self._uid

    # ---------------- router hand-off (serving/router.py) ---------- #
    def enqueue(self, req: Request,
                deadline_t: Optional[float] = None) -> int:
        """Insert a pre-built ``Request`` PRESERVING its uid — the
        router's placement path (router-global uids) and the re-prefill
        failover fallback both re-enqueue the same request object on a
        different replica.  ``deadline_t`` carries an absolute deadline
        already computed against the shared clock (failed-over work keeps
        its original deadline; None recomputes from the request's SLO
        fields as ``submit`` would)."""
        now = self.clock()
        if deadline_t is None:
            deadlines = []
            if req.deadline_ms is not None:
                deadlines.append(now + req.deadline_ms / 1e3)
            if req.slo_tokens_per_s:
                deadlines.append(now + req.n_tokens / req.slo_tokens_per_s)
            deadline_t = min(deadlines) if deadlines else None
        self._uid = max(self._uid, req.uid)   # keep submit() uids unique
        self._seq += 1
        self.metrics[req.uid] = {
            "arrival_t": now, "priority": req.priority, "seq": self._seq,
            "deadline_t": deadline_t,
            "finish_t": None, "deadline_hit": None, "preempted": 0,
            "shed": 0, "tenant": req.tenant,
        }
        if self.tenancy is not None:
            self.tenancy.note_enqueue(req.tenant)
        self._push(req)
        return req.uid

    def adopt(self, item: Union[Request, LaneSnapshot],
              row: Dict[str, Any]) -> None:
        """Requeue work migrated from another replica — a drained /
        failed-over ``LaneSnapshot`` or a still-queued ``Request`` —
        carrying its SLO bookkeeping row.  The row's absolute times are
        valid here because every replica of a router shares one clock;
        only the seq tie-break is re-stamped (per-replica counters
        collide), so adopt in the source's seq order to preserve
        relative arrival."""
        req = item.req if isinstance(item, LaneSnapshot) else item
        self._uid = max(self._uid, req.uid)
        self._seq += 1
        row = dict(row)
        row["seq"] = self._seq
        row.setdefault("tenant", req.tenant)
        self.metrics[req.uid] = row
        if self.tenancy is not None:
            self.tenancy.note_enqueue(req.tenant)
        self._push(item)

    def extract_pending(self) -> List[tuple]:
        """Drain the queue for redistribution (replica drain / death):
        returns ``[(item, metrics_row), ...]`` in queue-seq order and
        forgets the entries locally.  In-flight LANES are not touched —
        the caller suspends or abandons those separately."""
        entries = sorted(self.queue, key=lambda e: e[-2])
        self.queue = []
        out = []
        for e in entries:
            item = e[-1]
            req = item.req if isinstance(item, LaneSnapshot) else item
            out.append((item, self.metrics[req.uid]))
        return out

    # ---------------- server front end (serving/server.py) ---------- #
    def _remove_queued(self, uid: int) \
            -> Optional[Union[Request, LaneSnapshot]]:
        for i, e in enumerate(self.queue):
            item = e[-1]
            req = item.req if isinstance(item, LaneSnapshot) else item
            if req.uid == uid:
                self.queue.pop(i)
                heapq.heapify(self.queue)
                return item
        return None

    def _finish_cancelled(self, req: Request) -> None:
        self.done[req.uid] = req
        m = self.metrics[req.uid]
        m["finish_t"] = self.clock()
        m["deadline_hit"] = None      # cancelled: excluded from SLO stats
        self.n_cancelled += 1
        if self.tenancy is not None:
            n = 0 if req.result is None else int(len(req.result))
            self.tenancy.note_done(req.tenant, req.uid, n, cancelled=True)

    def cancel(self, uid: int) -> bool:
        """Cancel a live request (the server's client-disconnect path).
        A queued entry is removed — a suspended victim's snapshot is
        discarded through the engine, so its exported stash bytes
        release; a running lane goes through the engine's freeze-native
        ``cancel_request`` (suspend + drop).  Either way no scheduler
        entry is stranded: the uid lands in ``done`` with status
        ``CANCELLED`` and its partial tokens as the result.  Returns
        False when the uid already finished — including retiring during
        the cancel's own ring flush, in which case it is too late to
        cancel and the completed result surfaces via ``step`` as
        normal."""
        if uid in self.done or uid not in self.metrics:
            return False
        item = self._remove_queued(uid)
        if item is not None:
            req = item.req if isinstance(item, LaneSnapshot) else item
            if isinstance(item, LaneSnapshot):
                self.engine.discard_snapshot(item)
                req.result = np.asarray(item.generated[: req.n_tokens],
                                        np.int32)
            else:
                req.result = np.zeros(0, np.int32)
            req.status = RequestStatus.CANCELLED
            self._finish_cancelled(req)
            return True
        req = self.engine.cancel_request(uid)
        if req is None:
            return False
        self._finish_cancelled(req)
        return True

    def pause(self, uid: int) -> Optional[Union[Request, LaneSnapshot]]:
        """Freeze-native backpressure (the server's consumer queue is
        full): suspend the uid's lane — or pull its still-queued entry —
        and hand the item to the caller WITHOUT requeueing it, so the
        scheduler cannot resume it until the caller gives it back via
        :meth:`release`.  Returns None when the uid is not pauseable
        right now (already finishing, or mid-install on the paged
        engine)."""
        if uid in self.done or uid not in self.metrics:
            return None
        item = self._remove_queued(uid)
        if item is not None:
            return item
        eng = self.engine
        for i, l in enumerate(eng.lanes):
            if l.request is not None and l.request.uid == uid:
                t0 = self.clock()
                snap = eng.suspend_lane(i)
                self._obs("_suspend_s", self.clock() - t0)
                if snap is None:
                    return None           # retired during the flush
                if self.tenancy is not None:
                    self.tenancy.note_release(snap.req.tenant, uid)
                return snap
        return None

    def release(self, item: Union[Request, LaneSnapshot]) -> None:
        """Requeue a paused item (the consumer drained its queue)."""
        req = item.req if isinstance(item, LaneSnapshot) else item
        if self.tenancy is not None:
            self.tenancy.note_enqueue(req.tenant)
        self._push(item)

    # ---------------- admission + preemption ---------------- #
    def _admit_free(self) -> None:
        """Fill every free lane from the queue in policy order (resuming
        suspended victims through the engine's restore path).  Ladder
        stage 3+ (host-stash pressure at ``throttle_admissions``) holds
        the queue: every admission/resume brings more pages that will
        freeze into the already-over-budget stash, so new work waits
        until the pressure drains.  Queued requests are delayed, never
        altered.  The gate reads ``admission_pressure`` (stash PLUS
        exported snapshot bytes) rather than the raw stash gauge: a shed
        victim's export dips the gauge below the threshold for exactly
        as long as it stays suspended, and resuming it imports every
        byte back — hysteresis that stops the shed rung and this loop
        ping-ponging one lane's pages in and out of the store.  An IDLE
        engine is never throttled — with zero active
        lanes nothing can drain the pressure, so holding the queue would
        starve it forever (and the shed rung never takes the last running
        lane, so admit-then-shed cannot ping-pong a lone request).  The
        gate is re-checked per admission so the idle exemption admits
        exactly one item under pressure, not a full refill."""
        eng = self.engine
        admitted = 0
        while self.queue and eng.has_free_lane:
            if (eng.n_active_lanes + admitted) > 0 and \
                    eng.admission_pressure >= \
                    eng.ladder_cfg.throttle_admissions:
                eng.robust["ladder_throttle"] += 1
                return
            item = self._pop_admissible()
            if item is None:
                return                      # nothing quota-admissible
            req = item.req if isinstance(item, LaneSnapshot) else item
            if isinstance(item, LaneSnapshot):
                t0 = self.clock()
                eng.resume_lane(item)
                self._obs("_resume_s", self.clock() - t0)
            else:
                eng.admit(item)
            if self.tenancy is not None:
                self.tenancy.note_admit(req.tenant, req.uid)
            admitted += 1

    def _est_service_s(self, item: Union[Request, LaneSnapshot]) -> float:
        """Rough wall estimate to serve `item` from (re-)admission: chunked
        prefill steps (paged) or one blocking prefill (contiguous) plus
        one engine step per decode token.  A resumed snapshot on the paged
        engine needs no prefill and only its remaining tokens — its pool
        slice pushes straight back."""
        if self._step_s is None:
            return 0.0
        chunk = getattr(self.engine, "prefill_chunk", None)
        if isinstance(item, LaneSnapshot) and item.started:
            remaining = item.req.n_tokens - len(item.generated)
            pre = 0 if chunk else 1          # contiguous resume re-prefills
            return (pre + max(remaining, 0)) * self._step_s
        req = item.req if isinstance(item, LaneSnapshot) else item
        pre = math.ceil(len(req.prompt) / chunk) if chunk else 1
        return (pre + req.n_tokens) * self._step_s

    def _est_free_s(self, lanes: List[int]) -> float:
        """Estimated wall time until the first of `lanes` frees naturally
        (shortest remaining decode; the async pipeline's host view may lag
        one step — immaterial for an EMA-scaled estimate)."""
        if self._step_s is None or not lanes:
            return 0.0
        rem = min(self.engine.lanes[i].request.n_tokens
                  - len(self.engine.lanes[i].generated) for i in lanes)
        return max(rem, 0) * self._step_s

    def _obs(self, attr: str, dt: float) -> None:
        """Fold one wall-time observation into an EMA attribute (same
        0.7/0.3 blend as the step-time EMA)."""
        cur = getattr(self, attr)
        setattr(self, attr, dt if cur is None else 0.7 * cur + 0.3 * dt)

    def preempt_cost_s(self) -> float:
        """Predicted wall cost of one preemption cycle: suspending the
        victim now plus resuming its snapshot later, from the measured
        EMAs.  0.0 until both legs have been observed — a cost model
        calibrated from nothing would only ever veto, so the scheduler
        preempts freely first and lets the measurements argue back."""
        if self._suspend_s is None or self._resume_s is None:
            return 0.0
        return self._suspend_s + self._resume_s

    def _pick_victim(self, priority: int) -> Optional[int]:
        """The least valuable running lane strictly below `priority`:
        lowest class first, then fewest prior preemptions, then most
        remaining work (it would hold the lane longest), then latest
        deadline.  The prior-preemption key spreads victims across lanes
        — repeatedly preempting the same lane concentrates every inserted
        foreground on one lane's timeline, and the unmatched insertions
        surface later as an unpaired drain tail.  Lanes already being
        preempted into (a pending ``admit_over`` prefill) are not victims
        twice."""
        pending = getattr(self.engine, "prefills", {})
        best, best_rank = None, None
        for i, l in enumerate(self.engine.lanes):
            if l.request is None or l.request.priority <= priority \
                    or i in pending:
                continue
            dl = self._deadline_t(l.request.uid)
            rank = (-l.request.priority,
                    self.metrics[l.request.uid]["preempted"],
                    -(l.request.n_tokens - len(l.generated)),
                    -(dl if dl is not None else _INF))
            if best_rank is None or rank < best_rank:
                best, best_rank = i, rank
        return best

    def _maybe_preempt(self) -> None:
        """Preempt a running lane when the best pending request (a) has a
        deadline it is predicted to miss by waiting, and (b) a strictly
        lower-priority lane is running — at most one preemption per
        scheduling pass (one per engine step is plenty of cadence).
        Victims re-enter the queue as resumable ``LaneSnapshot``s under
        their own priority/deadline."""
        if not self.preemption:
            return
        if self.queue and not self.engine.has_free_lane:
            head = self._peek()
            req = head.req if isinstance(head, LaneSnapshot) else head
            dl = self._deadline_t(req.uid)
            if dl is None:
                return                      # no deadline -> no urgency
            if self.tenancy is not None \
                    and not self.tenancy.may_admit(req.tenant):
                return    # quota-blocked: a freed lane couldn't seat it
            running = [i for i, l in enumerate(self.engine.lanes)
                       if l.request is not None]
            wait = self._est_free_s(running)
            if self.clock() + wait + self._est_service_s(head) <= dl:
                return                      # on track without preempting
            # cost model: preempting buys at most `wait` (the natural
            # time-to-free) for the head, and costs a suspend now plus a
            # resume later.  When the overhead eats the whole gain the
            # preemption is pure churn — skip it and let the lane free
            # naturally.
            cost = self.preempt_cost_s()
            if cost > 0.0 and wait <= cost:
                self.n_preempt_skipped_cost += 1
                return
            victim = self._pick_victim(self._eff_priority(req))
            if victim is None:
                return                      # nothing less important runs
            if not isinstance(head, LaneSnapshot) \
                    and hasattr(self.engine, "admit_over"):
                # install-time preemption (paged engine): the preemptor's
                # prefill runs in scratch while the victim keeps decoding;
                # the victim's snapshot surfaces via drain_suspended()
                # once the prefill installs — preemption costs the victim
                # only the lane-time the preemptor actually decodes
                self._pop()
                self.engine.admit_over(req, victim)
            else:
                # immediate suspension: resuming a snapshot needs the lane
                # free NOW (its pool slice pushes right back), and the
                # contiguous engine has no scratch prefill to overlap
                vic = self.engine.lanes[victim].request
                t0 = self.clock()
                snap = self.engine.suspend_lane(victim)
                self._obs("_suspend_s", self.clock() - t0)
                if snap is not None:
                    self.metrics[vic.uid]["preempted"] += 1
                    self.n_preemptions += 1
                    if self.tenancy is not None:
                        self.tenancy.note_release(vic.tenant, vic.uid)
                    self._push(snap)
                # the freed lane is filled by the _admit_free that follows
            return

    def _maybe_shed(self) -> None:
        """Ladder stage 4 (load shed): suspend the least-valuable running
        lane through the freeze-native snapshot path and requeue it under
        its own priority/seq.  Shedding moves the lane's stash pages out
        of the controller store (``export_lane``), dropping the measured
        pressure immediately; the request resumes **token-identically**
        once the throttle rung clears, marked ``shed-resumed`` at
        retirement.  The last running lane is never shed — some lane must
        keep retiring work or the pressure could never drain."""
        eng = self.engine
        if eng.stash_pressure < eng.ladder_cfg.shed \
                or eng.n_active_lanes <= 1:
            return
        victim = self._pick_victim(-1)      # any running lane qualifies
        if victim is None:
            return
        req = self.engine.lanes[victim].request
        t0 = self.clock()
        snap = self.engine.suspend_lane(victim)
        self._obs("_suspend_s", self.clock() - t0)
        if snap is None:
            return                          # retired during the flush
        req.status = RequestStatus.SHED
        self.metrics[req.uid]["shed"] += 1
        self.engine.robust["ladder_shed"] += 1
        if self.tenancy is not None:
            self.tenancy.note_release(req.tenant, req.uid)
        self._push(snap)

    def _schedule(self) -> None:
        with jax.profiler.TraceAnnotation("repro:sched.schedule"):
            self._apply_aging()
            self._maybe_shed()
            self._maybe_preempt()
            self._admit_free()

    # ---------------- serving loop ---------------- #
    @property
    def busy(self) -> bool:
        """The engine still has work: active lanes, a pending chunked
        prefill (an ``admit_over`` whose victim retired mid-prefill holds
        no request yet, but its admission must still be driven home), or
        retirements parked in the engine's backlog.  The backlog term
        matters at shutdown: a request that retires during the flush
        inside ``suspend_lane`` is re-reported by the next ``step_once``
        — without it the loop could go idle at that exact moment and
        exit with the finished request stranded, never entering
        ``done``."""
        return self.engine.n_active_lanes > 0 \
            or bool(getattr(self.engine, "prefills", None)) \
            or self.engine.n_pending_retired > 0

    def step(self) -> List[int]:
        """One scheduling pass + one engine step; returns completed uids.
        The building block for external drivers with timed arrivals
        (``benchmarks/scheduling.py``).  Span: ``repro:sched.step``."""
        with jax.profiler.TraceAnnotation("repro:sched.step"):
            self._schedule()
            if not self.busy:
                return []
            t0 = self.clock()
            retired = self.engine.step_once()
            dt = self.clock() - t0
            self._step_s = dt if self._step_s is None \
                else 0.7 * self._step_s + 0.3 * dt
            if self.tenancy is not None:
                # charge each tenant the committed tokens its lanes gained
                # this step (delta-based: rewinds shrink `generated` and are
                # simply not refunded)
                for l in self.engine.lanes:
                    if l.request is not None:
                        self.tenancy.note_progress(
                            l.request.tenant, l.request.uid, len(l.generated))
            for snap in self.engine.drain_suspended():
                self.metrics[snap.req.uid]["preempted"] += 1
                self.n_preemptions += 1
                if self.tenancy is not None:
                    self.tenancy.note_progress(snap.req.tenant, snap.req.uid,
                                               len(snap.generated))
                    self.tenancy.note_release(snap.req.tenant, snap.req.uid)
                self._push(snap)
            out = []
            now = self.clock()
            for req in retired:
                self.done[req.uid] = req
                m = self.metrics[req.uid]
                m["finish_t"] = now
                dl = m["deadline_t"]
                m["deadline_hit"] = None if dl is None else bool(now <= dl)
                if self.tenancy is not None:
                    self.tenancy.note_done(req.tenant, req.uid,
                                           int(len(req.result)))
                out.append(req.uid)
            return out

    def run_once(self) -> List[int]:
        """Serve until at least one request completes (lanes refill from
        the queue as they free); returns the completed uids."""
        out: List[int] = []
        while not out:
            out = self.step()
            if not out and not self.busy:
                break
        return out

    def run(self) -> None:
        while self.queue or self.busy:
            if not self.run_once():
                break


class StaticScheduler:
    """Original static FIFO batcher (head-of-line blocking by design): pads
    a fixed batch, runs every lane for max(n_tokens) steps, then admits the
    next batch.  Kept as the benchmark baseline.  ``Engine.generate``
    applies ONE ``SamplingParams`` to the whole padded batch, so a batch
    mixing sampling configs is rejected loudly instead of silently decoding
    everyone with ``batch[0]``'s temperature — the limitation that
    motivated per-lane sampling in the continuous engine."""

    def __init__(self, engine: Engine, batch_size: int, pad_id: int = 0):
        self.engine = engine
        self.batch_size = batch_size
        self.pad_id = pad_id
        self.queue: List[Request] = []
        self.done: Dict[int, Request] = {}
        self._uid = 0

    def submit(self, prompt: np.ndarray, n_tokens: int,
               sampling: SamplingParams = SamplingParams()) -> int:
        self._uid += 1
        self.queue.append(Request(self._uid, np.asarray(prompt, np.int32),
                                  n_tokens, sampling))
        return self._uid

    def run_once(self) -> List[int]:
        """Serve one padded batch from the queue; returns completed uids."""
        if not self.queue:
            return []
        batch = self.queue[: self.batch_size]
        self.queue = self.queue[self.batch_size:]
        mixed = {r.sampling for r in batch}
        if len(mixed) > 1:
            raise ValueError(
                "StaticScheduler pads one jitted batch and Engine.generate "
                f"applies a single SamplingParams to all of it, but this "
                f"batch mixes {len(mixed)} configs: {sorted(map(str, mixed))}"
                ". Submit homogeneous batches or use the continuous "
                "Scheduler (per-lane sampling).")
        n_lanes = self.batch_size
        max_prompt = max(len(r.prompt) for r in batch)
        n_gen = max(r.n_tokens for r in batch)
        toks = np.full((n_lanes, max_prompt), self.pad_id, np.int32)
        for i, r in enumerate(batch):
            toks[i, max_prompt - len(r.prompt):] = r.prompt  # left-pad
        res = self.engine.generate({"tokens": jnp.asarray(toks)}, n_gen,
                                   sampling=batch[0].sampling)
        out = []
        for i, r in enumerate(batch):
            r.result = res.tokens[i, : r.n_tokens]
            self.done[r.uid] = r
            out.append(r.uid)
        return out

    def run(self) -> None:
        while self.queue:
            self.run_once()
