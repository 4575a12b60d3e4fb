"""ASR-KF-EGR serving engines.

Three generation drivers share the jitted prefill / decode-step cores:

* ``Engine`` — static one-shot batched generation: every lane starts
  together and runs for the same number of steps (benchmark arms, examples,
  the paper's Table 1 protocol).

* ``ContinuousEngine`` — continuous batching over a dense per-lane cache:
  a jitted per-step core with **per-lane** ``pos`` / ``step`` vectors plus
  a host-side lane manager.  Lanes admit a new request the moment their
  current one retires — mid-generation, without draining the batch — via a
  per-lane prefill-into-slot (``model.write_lane_state``).  Admission
  overwrites the lane's KV / freeze / recovery state wholesale, so no
  freeze counters or entropy baselines leak between requests sharing a
  lane.

* ``PagedContinuousEngine`` — the bounded-HBM production path: decode
  attends only each lane's O(P * page) device page pool, long prompts
  prefill in chunks interleaved with resident decode, frozen/overflow
  pages live in the host store, and entropy-guided recovery runs
  page-granular (stashed-page thaws + page-aware rewinds).

Host-side responsibilities beyond the jitted step (all drivers):
  * host residency of fully-frozen KV (the paper's "frozen storage F"):
    page-batched offload on the dense paths (cache.HostOffloadController)
    and per-page swap/stash/thaw on the paged path
    (core.paging.PagedController) — bookkeeping keyed per (layer, lane,
    page) so lane reuse can drop exactly its own pages
  * Rewalk Regeneration (recovery level 4): rewind ``rewalk_tokens``,
    clear freeze state (FR already applied in-step), re-decode — history,
    rewind budget and cooldown are tracked per lane; the paged path also
    invalidates the rewound KV slots / pages on device
  * telemetry: active/frozen KV trajectory (paper Fig. 1), compression
    ratio (Table 1), entropy/recovery events — one append per lane-step

**Async DMA pipeline** (both continuous engines, ``async_pipeline=True``):
the per-step device->host fetch (sampled tokens + telemetry + recovery
requests) is pushed into a double-buffered ring (``serving.dma.FetchRing``)
right behind the jitted step and *consumed at the start of the next engine
call* — the D2H copy overlaps the device compute and the host's
post-dispatch work instead of stalling right after dispatch.  Host
controller decisions (token commits, telemetry, thaw requests, rewinds,
retirement, offload) therefore run one step behind the device — the same
sliding-window slack the paper's schedule already tolerates — but in
exactly the order the synchronous path applies them, so the two modes
make identical host decisions (``async_pipeline=False`` runs the same
code with a depth-0 ring: push immediately followed by a blocking pop).
Output tokens are bit-identical whenever the prefill chunk split is
deterministic (``burst_prefill=False``): the modes admit on different
wall calls, and the load-adaptive burst split would change
flash-attention summation order — float rounding, not decisions.  The
paged engine additionally batches each boundary tick's pool slices into
ONE device_get / device_put pair across all boundary lanes and layers
(reused host staging buffers), pushes K/V back only when the tick actually
wrote some (metadata-only push otherwise), and speculatively uploads the
top-priority stashed pages into per-lane device *staging slots* so an
entropy-driven thaw becomes a page-table remap instead of a blocking
upload (``core.paging.PagedController.stage_slots`` / ``staged_keys``).

Every host->device upload goes through ``_upload``, which hands the
device a private copy of the host buffer.  ``jnp.asarray`` /
``device_put`` may alias a numpy buffer until the transfer completes,
and the engines refill their per-step vectors and reused staging buffers
while work dispatched from them can still be queued — an aliased upload
would then read the next step's values.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FreezeConfig, ModelConfig
from repro.core import quant
from repro.serving.config import ServingConfig, resolve_serving_config
from repro.core.cache import HostOffloadController, KVCache
from repro.core.paging import PagedController, PageFreezeState
from repro.core.recovery import RecoveryState
from repro.models import model as MD
from repro.serving.dma import FetchRing, HostStaging, TransferStats
from repro.serving.faults import ChaosConfig, Endpoint
from repro.serving.sampling import (SamplingParams, lane_base_key,
                                    params_arrays, sample,
                                    sample_batched_perlane)


def _upload(x) -> jax.Array:
    """Host -> device upload of a buffer the engine may refill before the
    dispatched work that reads it has run (see the module docstring)."""
    return jnp.asarray(np.array(x))


def _named(fn, name: str):
    """Name a ``functools.partial`` for ``jax.jit``: its program is then
    ``jit_<name>`` in a profiler trace, not ``jit__unknown``."""
    fn.__name__ = name
    return fn


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray                 # (B, n_generated)
    # per-step telemetry (paper Fig. 1 / Table 1)
    active_kv: List[float]             # mean active slots per layer/seq
    frozen_kv: List[float]
    total_kv: List[int]
    entropy: List[float]
    recovery_events: List[Dict[str, Any]]
    offloaded_tokens: List[int]
    rewinds: int = 0

    @property
    def compression(self) -> float:
        """Paper Table 1: 1 - active/total at the final step."""
        if not self.active_kv:
            return 0.0
        return 1.0 - self.active_kv[-1] / max(self.total_kv[-1], 1)


class RequestStatus(str, enum.Enum):
    """Request lifecycle status — ONE enum shared by the scheduler, both
    engines, the replica router and the HTTP server (it replaced the
    ad-hoc per-module status strings).

    A ``str`` subclass on purpose: every value equals its historical
    string (``RequestStatus.COMPLETED == "completed"``), so status
    comparisons in older call sites, JSON reports and sorted tallies are
    unchanged.  Lifecycle: requests are ``PENDING`` in flight (``SHED``
    while parked by the degradation ladder's load-shed rung); retirement
    resolves to ``COMPLETED``, ``SHED_RESUMED`` (completed after at least
    one shed/resume round trip) or ``QUARANTINED`` (retired early — the
    lane re-poisoned; the partial result is whatever survived the anomaly
    rewinds).  ``CANCELLED`` is terminal for a client-disconnected
    request whose lane was suspended and dropped."""
    PENDING = "pending"
    SHED = "shed"
    COMPLETED = "completed"
    SHED_RESUMED = "shed-resumed"
    QUARANTINED = "quarantined"
    CANCELLED = "cancelled"

    def __str__(self) -> str:       # "completed", never the member repr
        return self.value

    @property
    def terminal(self) -> bool:
        return self not in (RequestStatus.PENDING, RequestStatus.SHED)


@dataclasses.dataclass
class Request:
    """One generation request, as seen by the scheduler and lane manager.

    ``priority`` is a strict class (0 = most important; the scheduler never
    runs a class while a higher one is runnable and may *preempt* running
    lanes for it).  ``deadline_ms`` (relative to submission) and
    ``slo_tokens_per_s`` (a decode-rate SLO the scheduler converts into a
    completion deadline) order requests within a class — earliest deadline
    first.  All three default to "no SLO", under which the scheduler
    degrades to plain FIFO.  ``tenant`` tags the request for the
    tenancy layer's quota/fair-share accounting (None = untenanted,
    exempt from quotas)."""
    uid: int
    prompt: np.ndarray            # (S,) int32
    n_tokens: int
    sampling: SamplingParams = SamplingParams()
    priority: int = 0
    deadline_ms: Optional[float] = None
    slo_tokens_per_s: Optional[float] = None
    result: Optional[np.ndarray] = None
    telemetry: Optional[GenerationResult] = None
    status: RequestStatus = RequestStatus.PENDING
    tenant: Optional[str] = None


@dataclasses.dataclass
class LadderConfig:
    """Graceful-degradation ladder thresholds, as fractions of the
    host-stash budget (``stash_bytes / stash_budget_bytes``).  Each rung
    engages independently whenever pressure reaches ITS threshold — so a
    run can disable one rung by raising its threshold out of reach
    (e.g. ``deepen_timers=2.0`` for parity-critical serving) while the
    rungs around it keep working.  The defaults are ordered from
    parity-preserving to lossy:

    1. **deny prefetch** — stop speculative thaw staging and free the
       redundant host copies of device-resident pages (paged path) /
       stop offloading newly frozen pages (contiguous path).  Pure
       optimization rollback: token streams are unchanged.
    2. **deepen timers** — offloaded freeze timers decrement every other
       boundary tick, so stashed pages come home ~2x slower.  Changes
       page-visibility timing, so NOT token-parity-preserving; runs that
       must keep parity set this threshold above ``shed``.
    3. **throttle admissions** — the scheduler stops admitting/resuming
       work until pressure clears (queued requests are delayed, their
       tokens unchanged).
    4. **shed** — the scheduler suspends the lowest-priority running
       lane through the freeze-native ``suspend_lane`` snapshot path;
       the work resumes token-identically when pressure clears.
    """
    deny_prefetch: float = 0.60
    deepen_timers: float = 0.75
    throttle_admissions: float = 0.85
    shed: float = 0.95

    def stage(self, pressure: float) -> int:
        """Highest engaged rung (0 = nominal .. 4 = shed) — reporting
        only; rung decisions compare against their own thresholds."""
        if pressure >= self.shed:
            return 4
        if pressure >= self.throttle_admissions:
            return 3
        if pressure >= self.deepen_timers:
            return 2
        if pressure >= self.deny_prefetch:
            return 1
        return 0


@dataclasses.dataclass
class LaneSnapshot:
    """Resumable mid-generation state of a preempted lane.

    Produced by ``suspend_lane`` and consumed by ``resume_lane`` (possibly
    on a *different* lane slot).  The host-side fields (tokens, clocks,
    rewind budget, the snapshot-stable sampling base key) are common to
    both engines; the paged engine additionally carries the lane's entire
    pool slice + freeze state + recovery-ladder scalars and owns the
    lane's host-stashed pages, so resume restores a byte-identical device
    layout and the continuation is token-identical to the uninterrupted
    run.  The contiguous engine carries no KV (a dense lane slice is the
    whole ``max_seq`` cache) — it resumes by re-prefilling prompt +
    generated tokens, an approximate (freeze state restarts) but cheap
    fallback.

    A snapshot with ``generated == []`` marks an admission that was
    cancelled before its first token (e.g. mid-chunked-prefill): resume is
    a plain re-admit."""
    req: Request
    generated: List[int]
    history: List[Tuple[int, int]]
    pos: int
    step: int                      # decode clock (sampling folds it in)
    tok: int                       # next step's input token
    rewinds: int
    last_rewind_step: int
    lane_key: Optional[np.ndarray] = None    # (2,) uint32 sampling base
    # ---- paged-path payload (None on the contiguous fallback) ---- #
    pool: Optional[Dict[str, np.ndarray]] = None     # (L, 1, P_total, ...)
    fstate: Optional[Dict[str, np.ndarray]] = None
    recovery: Optional[Dict[str, Any]] = None        # ladder scalars
    tail_slot: Optional[np.ndarray] = None           # (L,) int32
    stashed: Optional[Dict[Tuple[int, int], Any]] = None  # host-store pages
    pending_thaw: bool = False
    urgency: float = 0.0
    # False for checkpoint snapshots (``checkpoint_lane``): the stashed
    # pages are shared copies still owned by the live controller, so no
    # ``exported_bytes`` accounting moved and none must move back on
    # resume/discard
    exported: bool = True

    @property
    def started(self) -> bool:
        """Whether any decode progress exists (False = resume re-admits)."""
        return bool(self.generated)


class Engine:
    """Static batched generation with ASR-KF-EGR freeze management."""

    def __init__(self, cfg: ModelConfig, params, max_seq: int,
                 freeze_cfg: Optional[FreezeConfig] = None,
                 enable_freeze: bool = True,
                 offload: bool = True,
                 max_rewinds: int = 4,
                 rewind_cooldown: int = 32):
        self.max_rewinds = max_rewinds
        self.rewind_cooldown = rewind_cooldown
        self.cfg = cfg
        self.params = params
        self.max_seq = max_seq
        self.fcfg = freeze_cfg or cfg.freeze
        self.enable_freeze = enable_freeze
        self.offload = offload and enable_freeze
        # donate the decode state: KV / freeze buffers are updated in place
        # instead of double-buffered in HBM (on backends without donation
        # support, e.g. CPU, JAX falls back to copies with a warning)
        self._prefill = jax.jit(
            functools.partial(MD.prefill, cfg=cfg),
            donate_argnames=("state",))
        self._step = jax.jit(functools.partial(
            MD.decode_step, cfg=cfg, freeze_cfg=self.fcfg,
            enable_freeze=enable_freeze), donate_argnames=("state",))

    def generate(self, batch: Dict[str, jnp.ndarray], n_tokens: int,
                 sampling: SamplingParams = SamplingParams(),
                 seed: int = 0) -> GenerationResult:
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S0 = tokens.shape
        assert S0 + n_tokens <= self.max_seq
        state = MD.init_decode_state(cfg, B, self.max_seq)
        logits, state = self._prefill(self.params, batch=batch, state=state)
        key = jax.random.PRNGKey(seed)
        res = GenerationResult([], [], [], [], [], [], [])
        offloader = HostOffloadController(self.fcfg.page_size) \
            if self.offload else None

        out_tokens = []
        history: List[jnp.ndarray] = []   # (token, pos) for rewind
        pos, step = S0, 0
        last_rewind_step = -10**9
        key, sub = jax.random.split(key)
        tok = sample(logits, sub, sampling)
        out_tokens.append(np.asarray(tok))
        while len(out_tokens) < n_tokens:
            logits, state, info = self._step(
                self.params, token=tok, pos=jnp.int32(pos),
                step=jnp.int32(step), state=state)
            # ---- telemetry (every list appends exactly once per step) ----
            n_layers_attn = max(state.freeze.frozen.shape[0], 1) \
                if hasattr(state, "freeze") else 1
            if "n_active" in info:
                denom = n_layers_attn * B
                res.active_kv.append(float(jnp.sum(info["n_active"])) / denom)
                res.frozen_kv.append(float(jnp.sum(info["n_frozen"])) / denom)
            else:
                res.active_kv.append(float(pos + 1))
                res.frozen_kv.append(0.0)
            res.total_kv.append(pos + 1)
            if "entropy" in info:
                res.entropy.append(float(jnp.mean(info["entropy"])))
                if bool(jnp.any(info["spike"])):
                    res.recovery_events.append({
                        "step": step,
                        "level": int(jnp.max(info["level"])),
                        "entropy": float(jnp.max(info["entropy"])),
                    })
            # ---- Rewalk Regeneration (recovery level 4) ----
            if "rr_request" in info and bool(jnp.any(info["rr_request"])) \
                    and len(history) >= self.fcfg.rewalk_tokens \
                    and res.rewinds < self.max_rewinds \
                    and step - last_rewind_step >= self.rewind_cooldown:
                nback = self.fcfg.rewalk_tokens
                del history[-nback:]
                del out_tokens[-nback:]
                pos -= nback
                res.rewinds += 1
                last_rewind_step = step
                # the input at the rewind point: the last surviving history
                # entry, or the prefill-sampled first token when the rewind
                # consumed the whole history (out_tokens[0] survives)
                tok = history[-1][0] if history \
                    else jnp.asarray(out_tokens[-1])
                step += 1
                res.offloaded_tokens.append(
                    offloader.offloaded_tokens if offloader else 0)
                continue
            # ---- host offload of fully-frozen pages ----
            if offloader is not None and step % 8 == 7:
                cache = KVCache(k=state.cache_k, v=state.cache_v)
                cache = offloader.sync(cache, np.asarray(state.freeze.frozen))
                state = state._replace(cache_k=cache.k, cache_v=cache.v)
            res.offloaded_tokens.append(
                offloader.offloaded_tokens if offloader else 0)

            key, sub = jax.random.split(key)
            tok = sample(logits, sub, sampling)
            history.append((tok, pos))
            out_tokens.append(np.asarray(tok))
            pos += 1
            step += 1
        res.tokens = np.stack(out_tokens, axis=1)
        return res


# ===================================================================== #
# Continuous batching
# ===================================================================== #
@dataclasses.dataclass
class _Lane:
    """Host-side bookkeeping for one batch slot of the jitted step."""
    request: Optional[Request] = None
    generated: List[int] = dataclasses.field(default_factory=list)
    history: List[Tuple[int, int]] = \
        dataclasses.field(default_factory=list)      # (token, pos) for rewind
    rewinds: int = 0
    last_rewind_step: int = -10**9


class _LaneEngineBase:
    """Shared lane management for the continuous-batching engines: lane
    accounting, prompt bucketing, per-lane sampling-parameter mirrors and
    the admit/finish event log.  Subclasses own the decode state layout
    (contiguous vs paged) and the step/admission mechanics."""

    def __init__(self, cfg: ModelConfig, params, serving: ServingConfig):
        assert not cfg.is_encoder_decoder, \
            "continuous batching is decoder-only (enc-dec uses Engine)"
        sv = serving
        max_seq, n_lanes = sv.max_seq, sv.n_lanes
        pad_id, seed = sv.pad_id, sv.seed
        async_pipeline, chaos = sv.async_pipeline, sv.chaos
        self.cfg = cfg
        self.params = params
        self.serving = sv
        self.max_seq = max_seq
        self.n_lanes = n_lanes
        self.fcfg = sv.freeze_cfg or cfg.freeze
        self.enable_freeze = sv.enable_freeze
        self.pad_id = pad_id
        self.min_prompt_bucket = sv.min_prompt_bucket
        self._sample = jax.jit(sample_batched_perlane)
        self.lanes = [_Lane() for _ in range(n_lanes)]
        self.pos = np.zeros(n_lanes, np.int32)
        self.step = np.zeros(n_lanes, np.int32)
        self.tok = np.full(n_lanes, pad_id, np.int32)
        greedy = SamplingParams.greedy()
        self._temp, self._topk, self._topp = (
            np.array(a) for a in params_arrays([greedy] * n_lanes))
        self._lane_params_dev = None     # device mirror, refreshed on admit
        self.key = jax.random.PRNGKey(seed)
        # order-invariant sampling randomness: the j-th *admission* gets a
        # base key (fold of the engine seed with the admission counter)
        # and every draw folds it with the lane's own decode clock — a
        # lane's token at logical step k is therefore independent of
        # which global dispatch carried it, which is what keeps the async
        # pipeline (whose admit/step interleaving differs from the sync
        # path's) token-for-token identical
        self._admit_count = 0
        self.lane_keys = np.array(
            jax.random.split(jax.random.PRNGKey(seed), n_lanes), np.uint32)
        self.wall_step = 0          # number of jitted decode steps issued
        self.events: List[Dict[str, Any]] = []   # admit / finish log
        self.peak_kv_bytes = 0      # high-water device KV (incl. prefill
                                    # scratch) — the benchmark memory metric
        # ---- async DMA pipeline (serving/dma.py) ---- #
        # Depth-1 ring: step N's fetch is issued right behind the dispatch
        # and consumed at the start of engine call N+1.  Depth 0 is the
        # synchronous baseline (push + blocking pop in the same call);
        # both modes drain entries in identical FIFO order, so their token
        # streams and telemetry are bit-identical.
        self.async_pipeline = async_pipeline
        self.stats = TransferStats()
        # ---- fault tolerance (serving/faults.py) ---- #
        # One injector (shared per-site op clocks) + one endpoint per
        # guarded transfer class.  pull/push/ring must succeed (the data
        # has to move); stage is best-effort (a failed speculative-thaw
        # staging just falls back to the sync upload path).  All None
        # without a chaos config — the hot path pays one attr check.
        self.chaos = chaos
        self._endpoints: Dict[str, Endpoint] = {}
        if chaos is not None:
            self.injector = chaos.build_injector()
            self.ep_pull = chaos.build_endpoint("pull", self.injector)
            self.ep_push = chaos.build_endpoint("push", self.injector)
            self.ep_ring = chaos.build_endpoint("ring", self.injector)
            self.ep_stage = chaos.build_endpoint("stage", self.injector,
                                                 must_succeed=False)
            self._endpoints = {"pull": self.ep_pull, "push": self.ep_push,
                               "ring": self.ep_ring, "stage": self.ep_stage}
        else:
            self.injector = None
            self.ep_pull = self.ep_push = None
            self.ep_ring = self.ep_stage = None
        # ---- host-stash budget + degradation ladder ---- #
        self.stash_budget_bytes = sv.stash_budget_bytes
        self.ladder_cfg = sv.ladder or LadderConfig()
        self.peak_stash_bytes = 0
        # ---- lane-level anomaly quarantine ---- #
        # A non-finite-entropy step triggers a bounded rewind-and-retry;
        # a lane that re-poisons within `quarantine_window` decode steps
        # of its last quarantine rewind is retired "quarantined" instead
        # of corrupting its batch peers' wall time any further.
        self.quarantine_window = sv.quarantine_window
        self._last_quarantine = np.full(n_lanes, -10**9, np.int64)
        self.robust = {"quarantine_rewinds": 0, "quarantined": 0,
                       "ladder_deny": 0, "ladder_deepen": 0,
                       "ladder_throttle": 0, "ladder_shed": 0}
        self.ring = FetchRing(self.stats, depth=1 if async_pipeline else 0,
                              endpoint=self.ep_ring)
        self.staging = HostStaging()
        self._retired_backlog: List[Request] = []   # retired during admit
                                    # drains; reported by the next step_once
        self._suspended: List[LaneSnapshot] = []    # victims of deferred
                                    # (install-time) preemption, awaiting
                                    # pickup via drain_suspended()

    @property
    def kv_device_bytes(self) -> int:       # subclasses override
        return 0

    def _note_kv_peak(self, scratch_bytes: int = 0) -> None:
        self.peak_kv_bytes = max(self.peak_kv_bytes,
                                 self.kv_device_bytes + scratch_bytes)

    # ---------------- robustness: budget ladder + fault plumbing -------- #
    def _stash_bytes(self) -> int:          # subclasses override
        return 0

    def _exported_bytes(self) -> int:       # subclasses override
        return 0

    @property
    def stash_pressure(self) -> float:
        """Measured host-stash bytes over the configured budget (0.0 when
        unbounded) — the degradation ladder's input."""
        if not self.stash_budget_bytes:
            return 0.0
        return self._stash_bytes() / self.stash_budget_bytes

    @property
    def admission_pressure(self) -> float:
        """Stash pressure as *admission* decisions must see it: measured
        stash bytes PLUS the pages suspended snapshots carried out via
        ``export_lane``.  Exporting a victim drops ``stash_pressure``
        instantly, but resuming the snapshot imports every one of those
        bytes straight back — gating admissions on the measured gauge
        alone lets a shed victim resume the same pass it was shed
        (export -> pressure dips -> resume -> import -> pressure pops ->
        shed again), an export/import ping-pong that makes no progress.
        Counting exported bytes gives the throttle rung hysteresis: a
        shed snapshot stays queued until real work retires and drains
        the stash."""
        if not self.stash_budget_bytes:
            return 0.0
        return (self._stash_bytes() + self._exported_bytes()) \
            / self.stash_budget_bytes

    @property
    def n_pending_retired(self) -> int:
        """Requests that already retired inside an admit/suspend flush,
        parked for re-report by the next ``step_once``.  The scheduler
        must keep stepping while this is non-zero or the retirements
        (and their results) would be stranded unreported."""
        return len(self._retired_backlog)

    @property
    def ladder_stage(self) -> int:
        """Current graceful-degradation stage (0 = nominal .. 4 = shed);
        see ``LadderConfig``.  The engine applies stages 1-2 itself; the
        scheduler reads this property for stages 3-4 (throttle / shed)."""
        return self.ladder_cfg.stage(self.stash_pressure)

    def _note_stash_peak(self) -> None:
        self.peak_stash_bytes = max(self.peak_stash_bytes,
                                    self._stash_bytes())

    def _ring_guard(self) -> None:
        """Degrade the fetch ring to its depth-0 synchronous baseline
        while the ring endpoint's breaker is tripped (and restore depth 1
        once it re-closes).  Depth only changes which engine call drains
        an entry, never the FIFO order, so the fallback is
        token-identical by the ring's design."""
        ep = self.ring.endpoint
        if ep is None or ep.breaker is None:
            return
        if ep.breaker.state == "open":
            ep.allow()          # burn one op of the op-count cooldown
        self.ring.depth = 1 if (self.async_pipeline
                                and ep.breaker.state == "closed") else 0

    def _poison_lane(self, active: List[int]) -> Optional[int]:
        """Consult the fault schedule's ``nan`` site for this dispatch.
        Returns the lane whose host-side entropy the commit will poison
        (None almost always).  Host-side by necessity: entropy is
        computed inside the jitted step from the real logits, so the
        injection happens where the poisoned value would first become
        visible to the host — the ring commit."""
        if self.injector is None or not active:
            return None
        plan = self.injector.next_plan("nan")
        if plan is None or plan.kind != "nan":
            return None
        return plan.lane if plan.lane in active else active[0]

    def discard_snapshot(self, snap: LaneSnapshot) -> None:
        """Release the host-side resources of a snapshot that will never
        resume (a suspended request that was cancelled / abandoned).  The
        contiguous snapshot owns nothing beyond host bookkeeping; the
        paged override returns the exported pages' byte accounting."""

    # ---------------- client-disconnect cancellation ---------------- #
    def cancel_lane(self, lane: int) -> Optional[Request]:
        """Cancel the lane's in-flight request (client disconnect) through
        the freeze-native drop path: ``suspend_lane`` (which flushes the
        ring, stashes/cancels exactly as a preemption would, and frees the
        lane) followed immediately by ``discard_snapshot`` (which returns
        the exported pages' byte accounting so nothing leaks).  The
        request keeps its partial tokens as ``result`` and ends
        ``CANCELLED``.  Returns None when the request retired during the
        suspend flush — the retirement is re-reported by the next
        ``step_once`` and cancellation lost the race to completion."""
        l = self.lanes[lane]
        if l.request is None and lane not in getattr(self, "prefills", {}):
            return None
        snap = self.suspend_lane(lane)
        if snap is None:
            return None
        self.discard_snapshot(snap)
        req = snap.req
        req.status = RequestStatus.CANCELLED
        req.result = np.asarray(snap.generated[: req.n_tokens], np.int32)
        self.events.append({"event": "cancel", "uid": req.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "generated": len(snap.generated)})
        return req

    def cancel_request(self, uid: int) -> Optional[Request]:
        """Find and cancel the lane running ``uid`` (the paged override
        also covers a preemptor still mid-``admit_over`` prefill)."""
        for i, l in enumerate(self.lanes):
            if l.request is not None and l.request.uid == uid:
                return self.cancel_lane(i)
        return None

    def robust_snapshot(self) -> Dict[str, Any]:
        """Fault/ladder/quarantine counters for benchmarks and serving
        reports (chaos-less engines report zeros)."""
        eps = {name: ep.stats() for name, ep in self._endpoints.items()}
        return {
            "endpoints": eps,
            "injected": self.injector.n_injected if self.injector else 0,
            "injected_by_site":
                dict(self.injector.injected) if self.injector else {},
            "retries": sum(e["retries"] for e in eps.values()),
            "breaker_trips": sum(e["breaker_trips"] for e in eps.values()),
            "ladder_stage": self.ladder_stage,
            "stash_bytes": self._stash_bytes(),
            "exported_bytes": self._exported_bytes(),
            "peak_stash_bytes": self.peak_stash_bytes,
            "stash_budget_bytes": self.stash_budget_bytes,
            **self.robust,
        }

    @staticmethod
    def _finalize_status(req: Request) -> None:
        """Map a retiring request's lifecycle status to its terminal
        value (quarantine retirement overwrites it afterwards)."""
        if req.status == RequestStatus.SHED:
            req.status = RequestStatus.SHED_RESUMED
        elif req.status == RequestStatus.PENDING:
            req.status = RequestStatus.COMPLETED

    def _quarantine_rewind(self, lane: int) -> bool:
        """Attempt the engine's page-aware rewind for a quarantined lane;
        True iff the lane state was actually rewound."""
        self._rewind_bookkeeping(lane)
        return True

    def _quarantine_scan(self, active: List[int], entropy,
                         rewound: set) -> List[Request]:
        """Lane-level anomaly quarantine: a lane whose committed entropy
        is non-finite (NaN/Inf logits) gets ONE bounded rewind-and-retry
        through the engine's Rewalk machinery; a lane that re-poisons
        within ``quarantine_window`` steps of its last quarantine rewind
        is beyond retry and is retired with status ``quarantined`` so its
        fault cannot poison telemetry or downstream commits.  Returns the
        retired requests; rewound lanes are added to ``rewound`` so the
        caller's commit loop discards their sampled token."""
        retired: List[Request] = []
        if entropy is None:
            return retired
        for i in active:
            l = self.lanes[i]
            if i in rewound or l.request is None \
                    or bool(np.isfinite(entropy[i])):
                continue
            recent = int(self.step[i]) - int(self._last_quarantine[i]) \
                <= self.quarantine_window
            if not recent and len(l.history) >= self.fcfg.rewalk_tokens \
                    and self._quarantine_rewind(i):
                self._last_quarantine[i] = int(self.step[i])
                self.robust["quarantine_rewinds"] += 1
                rewound.add(i)
            else:
                req = self._retire(i)
                req.status = RequestStatus.QUARANTINED
                self.robust["quarantined"] += 1
                retired.append(req)
        return retired

    # ---------------- lane accounting ---------------- #
    @property
    def n_active_lanes(self) -> int:
        return sum(1 for l in self.lanes if l.request is not None)

    @property
    def has_free_lane(self) -> bool:
        return any(l.request is None for l in self.lanes)

    def health(self) -> Dict[str, Any]:
        """Replica-facing liveness/occupancy facade, read by the router's
        placement scorer and heartbeat monitor.  Host-side gauges only —
        no device sync."""
        return {
            "wall_step": self.wall_step,
            "n_lanes": self.n_lanes,
            "n_active_lanes": self.n_active_lanes,
            "has_free_lane": self.has_free_lane,
            "admission_pressure": self.admission_pressure,
            "ladder_stage": self.ladder_stage,
            "active_uids": sorted(l.request.uid for l in self.lanes
                                  if l.request is not None),
        }

    def _free_lane(self) -> int:
        for i, l in enumerate(self.lanes):
            if l.request is None:
                return i
        raise RuntimeError("no free lane")

    def _bucket(self, prompt_len: int, n_tokens: int) -> int:
        """Pad the prompt to a power-of-two bucket (bounded prefill
        recompiles), falling back to the exact length when the bucket
        would not leave room for generation."""
        b = self.min_prompt_bucket
        while b < prompt_len:
            b *= 2
        if b + n_tokens > self.max_seq:
            b = prompt_len
        if b + n_tokens > self.max_seq:
            raise ValueError(
                f"request needs {prompt_len} prompt + {n_tokens} generated "
                f"slots but the engine was built with max_seq={self.max_seq}")
        return b

    def _set_lane_sampling(self, lane: int, sp: SamplingParams) -> None:
        self._temp[lane] = sp.temperature
        self._topk[lane] = sp.top_k
        self._topp[lane] = sp.top_p
        self._lane_params_dev = None

    def _lane_params(self):
        if self._lane_params_dev is None:
            self._lane_params_dev = (_upload(self._temp),
                                     _upload(self._topk),
                                     _upload(self._topp))
        return self._lane_params_dev

    def _left_padded(self, prompt: np.ndarray, sp: int) -> np.ndarray:
        toks = np.full((1, sp), self.pad_id, np.int32)
        toks[0, sp - len(prompt):] = prompt
        return toks

    def _rewind_bookkeeping(self, lane: int) -> None:
        """Shared RR host bookkeeping: truncate the rolled-back tokens,
        charge the lane's rewind budget/cooldown, and restore the input
        token at the rewind point — the last surviving history entry, or
        the admission-time first token (``generated[0]`` survives the
        truncation) when the rewind consumed the whole history.  The
        contiguous and paged engines must stay semantically identical
        here — the paged-vs-contiguous parity test depends on it."""
        l = self.lanes[lane]
        nback = self.fcfg.rewalk_tokens
        del l.history[-nback:]
        del l.generated[-nback:]
        self.pos[lane] -= nback
        l.rewinds += 1
        l.last_rewind_step = int(self.step[lane])
        l.request.telemetry.rewinds += 1
        self.tok[lane] = l.history[-1][0] if l.history else l.generated[-1]
        self.step[lane] += 1

    # ---------------- fetch-ring drain (shared pipeline) ---------------- #
    def _drain_ring(self) -> List[Request]:
        """Materialize every pending ring entry (FIFO) and apply the host
        bookkeeping it carries: admit-token commits, per-step telemetry,
        recovery servicing, token commits and retirement.  Runs at the
        start of every ``step_once`` (and at the end too when the pipeline
        is synchronous), so host decisions are applied in the same order
        in both modes."""
        finished: List[Request] = []
        with jax.profiler.TraceAnnotation("repro:engine.drain"):
            for meta, host in self.ring.drain():
                with jax.profiler.TraceAnnotation("repro:engine.commit"):
                    if meta["kind"] == "admit":
                        finished.extend(self._commit_admit(meta, host))
                    else:
                        finished.extend(self._commit_step(meta, host))
        return finished

    def flush(self) -> List[Request]:
        """Public drain: block until every in-flight fetch has landed and
        its bookkeeping is applied.  Call before reading per-lane host
        state (``pos`` / ``generated`` / telemetry) mid-run or before
        mutating engine state from outside ``step_once``.  Requests that
        retire during the flush are returned AND re-reported by the next
        ``step_once`` (via the backlog), so a scheduler driving the
        engine never misses one."""
        out = self._drain_ring()
        self._retired_backlog += out
        return out

    def _commit_admit(self, meta: Dict[str, Any], host: Dict[str, Any]
                      ) -> List[Request]:
        """Commit an admission's deferred first token (sampled from the
        prefill logits on device; the old path blocked the admission on
        ``int(np.asarray(...))`` of it).  The token enters ``generated``
        one drain late, by which point the prefill compute and the D2H
        copy have long overlapped other work."""
        lane = meta["lane"]
        l = self.lanes[lane]
        if l.request is not meta["req"]:        # lane was reset meanwhile
            return []
        first = int(host["tok"][0])
        self.tok[lane] = first
        l.generated = [first]
        if len(l.generated) >= l.request.n_tokens:
            return [self._retire(lane)]
        return []

    def _commit_step(self, meta: Dict[str, Any], host: Dict[str, Any]
                     ) -> List[Request]:
        raise NotImplementedError

    def _next_lane_key(self, lane: int):
        """Assign the lane its admission-ordered sampling base key (the
        admission sequence is identical in the sync and async pipelines,
        so this is order-invariant where a global split-per-dispatch
        stream would not be).  The first token folds in 2**31-1; decode
        steps fold in the lane's own clock (always < 2**31-1).  A
        *resumed* lane restores its snapshot's key instead of consuming a
        fresh admission index (``sampling.lane_base_key``)."""
        self._admit_count += 1
        base = lane_base_key(self.key, self._admit_count)
        self.lane_keys[lane] = np.asarray(base, np.uint32)
        return base

    # ---------------- preemption (suspend / resume) ---------------- #
    def _snap_host(self, lane: int) -> LaneSnapshot:
        """Capture the lane's host-side bookkeeping into a snapshot (the
        fields both engines share); the caller adds any engine-specific
        payload.  Must run after ``flush()`` — pending ring entries carry
        exactly this state."""
        l = self.lanes[lane]
        return LaneSnapshot(
            req=l.request, generated=list(l.generated),
            history=list(l.history), pos=int(self.pos[lane]),
            step=int(self.step[lane]), tok=int(self.tok[lane]),
            rewinds=l.rewinds, last_rewind_step=l.last_rewind_step,
            lane_key=self.lane_keys[lane].copy())

    def _restore_host(self, snap: LaneSnapshot, lane: int) -> None:
        """Inverse of ``_snap_host``: reinstall the shared host-side lane
        bookkeeping (clocks, tokens, rewind budget, the snapshot-stable
        sampling key and per-lane sampling params)."""
        l = self.lanes[lane]
        l.request = snap.req
        l.generated = list(snap.generated)
        l.history = list(snap.history)
        l.rewinds = snap.rewinds
        l.last_rewind_step = snap.last_rewind_step
        self.pos[lane] = snap.pos
        self.step[lane] = snap.step
        self.tok[lane] = snap.tok
        self.lane_keys[lane] = np.asarray(snap.lane_key, np.uint32)
        self._set_lane_sampling(lane, snap.req.sampling)

    def _park_lane(self, lane: int) -> None:
        """Leave a just-vacated lane idle: greedy sampling so the garbage
        it decodes is cheap, position clamped in-bounds."""
        l = self.lanes[lane]
        l.request = None
        l.generated = []
        l.history = []
        self._set_lane_sampling(lane, SamplingParams.greedy())
        self.pos[lane] = min(int(self.pos[lane]), self.max_seq - 1)

    def drain_suspended(self) -> List[LaneSnapshot]:
        """Collect (and clear) the snapshots of lanes the engine suspended
        on its own — currently only the paged engine's install-time
        preemption (``admit_over``).  A scheduler driving the engine must
        call this after every ``step_once`` and requeue the snapshots, or
        the victims' requests are lost."""
        out, self._suspended = self._suspended, []
        return out

    def _push_admit_token(self, lane: int, req: Request, logits) -> None:
        """Shared deferred first-token path: assign the lane's base key,
        sample the admission token on device right behind the prefill
        chain (never materializing it here — the old path blocked on
        ``int(np.asarray(...))``), install the lane's sampling params and
        push the token into the fetch ring for ``_commit_admit``.  Both
        engines MUST use this helper — the 2**31-1 fold sentinel and the
        entry shape are parity-critical with the base-class commit."""
        base = self._next_lane_key(lane)
        first_dev = sample(logits, jax.random.fold_in(base, 2**31 - 1),
                           req.sampling)
        self._set_lane_sampling(lane, req.sampling)
        self.ring.push({"kind": "admit", "lane": lane, "req": req},
                       {"tok": first_dev})


class ContinuousEngine(_LaneEngineBase):
    """Continuous-batching generation: per-lane admission and retirement.

    The jitted step always runs the full ``n_lanes``-wide batch (fixed
    shapes, one compile); idle lanes decode garbage that the host ignores.
    Prompt lengths are padded to power-of-two buckets so the per-lane
    prefill compiles O(log max_seq) times, not once per prompt length.
    """

    def __init__(self, cfg: ModelConfig, params,
                 max_seq: Optional[int] = None,
                 n_lanes: Optional[int] = None,
                 serving: Optional[ServingConfig] = None,
                 **legacy):
        sv = resolve_serving_config(serving, "contiguous", max_seq, n_lanes,
                                    legacy)
        super().__init__(cfg, params, sv)
        quant.resolve_mode(sv.kv_quant)
        self.kv_quant = sv.kv_quant
        self.max_rewinds = sv.max_rewinds
        self.rewind_cooldown = sv.rewind_cooldown
        # legacy knob, no longer a wall-clock cadence: the freeze mask now
        # rides the per-step fetch ring (~KBs) and `needs_sync` triggers
        # the cache round-trip exactly when a page crosses fully-frozen —
        # retained so existing callers keep constructing
        self.offload_every = sv.offload_every
        self.debug_lane_checks = sv.debug_lane_checks
        # donated decode state: the per-step KV/freeze buffers are reused in
        # place rather than double-buffered in HBM (no-op on CPU)
        self._prefill = jax.jit(functools.partial(MD.prefill, cfg=cfg),
                                donate_argnames=("state",))
        self._step = jax.jit(functools.partial(
            MD.decode_step, cfg=cfg, freeze_cfg=self.fcfg,
            enable_freeze=self.enable_freeze), donate_argnames=("state",))
        self._write_lane = jax.jit(functools.partial(MD.write_lane_state, cfg),
                                   donate_argnames=("state", "lane_state"))
        self.state = MD.init_decode_state(cfg, self.n_lanes, self.max_seq)
        self.offloader = HostOffloadController(self.fcfg.page_size) \
            if (sv.offload and self.enable_freeze) else None
        if self.offloader is not None:
            self.offloader.stash_budget_bytes = sv.stash_budget_bytes
            self.offloader.kv_quant = sv.kv_quant

    def _stash_bytes(self) -> int:
        return self.offloader.stash_bytes if self.offloader else 0

    @classmethod
    def from_engine(cls, engine: Engine, n_lanes: int,
                    **kw) -> "ContinuousEngine":
        """Build a continuous engine sharing a static Engine's model and
        freeze settings (the Scheduler's compatibility path)."""
        sv = ServingConfig(max_seq=engine.max_seq, n_lanes=n_lanes,
                           freeze_cfg=engine.fcfg,
                           enable_freeze=engine.enable_freeze,
                           offload=engine.offload,
                           max_rewinds=engine.max_rewinds,
                           rewind_cooldown=engine.rewind_cooldown, **kw)
        return cls(engine.cfg, engine.params, serving=sv)

    @property
    def kv_device_bytes(self) -> int:
        """Live device KV footprint (the benchmark's peak-memory metric)."""
        return self.state.cache_k.nbytes + self.state.cache_v.nbytes

    # ---------------- admission ---------------- #
    def admit(self, req: Request, lane: Optional[int] = None) -> int:
        """Prefill `req` into a free lane mid-stream.  The single-lane
        prefill state is scattered over the lane's slice of the batched
        decode state, which wholesale-resets its KV cache, freeze masks and
        recovery ladder; host-side page-offload bookkeeping for the lane's
        previous occupant is dropped."""
        # drain first: a pending ring entry may reference state buffers
        # (the folded-in offload freeze mask) that the admission scatter
        # donates below — and the sync path processes step N before any
        # later admission anyway, so ordering is unchanged.  This is also
        # what lets _commit_step trust its entry wholesale: no ring entry
        # ever spans an admission, so the lanes and freeze mask it carries
        # always describe the current occupants
        self._retired_backlog += self._drain_ring()
        if lane is None:
            lane = self._free_lane()
        l = self.lanes[lane]
        assert l.request is None, f"lane {lane} is busy"
        prompt = np.asarray(req.prompt, np.int32)
        sp = self._bucket(len(prompt), req.n_tokens)
        toks = self._left_padded(prompt, sp)          # left-pad, as in prefill
        event = {"event": "admit", "uid": req.uid, "lane": lane,
                 "wall_step": self.wall_step}
        if self.debug_lane_checks:
            # ONE batched pull for both debug fields (was two separate
            # blocking np.asarray materializations of full-state columns)
            # hotpath: ok(debug_lane_checks lane audit, default-off in serving)
            fro, seen = jax.device_get(
                (self.state.freeze.frozen[:, lane],
                 self.state.recovery.steps_seen[lane]))
            event["frozen_before"] = int(fro.sum())
            event["recovery_steps_before"] = int(seen)
        lane_state = MD.init_decode_state(self.cfg, 1, self.max_seq)
        self._note_kv_peak(lane_state.cache_k.nbytes + lane_state.cache_v.nbytes)
        logits, lane_state = self._prefill(
            self.params, batch={"tokens": _upload(toks)}, state=lane_state)
        self.state = self._write_lane(self.state, lane_state, jnp.int32(lane))
        if self.offloader is not None:
            self.offloader.drop_lane(lane)
        if self.debug_lane_checks:
            # hotpath: ok(debug_lane_checks lane audit, default-off in serving)
            fro, seen = jax.device_get(
                (self.state.freeze.frozen[:, lane],
                 self.state.recovery.steps_seen[lane]))
            event["frozen_after"] = int(fro.sum())
            event["recovery_steps_after"] = int(seen)
        self.pos[lane] = sp
        self.step[lane] = 0
        l.request = req
        l.generated = []
        l.history = []
        l.rewinds = 0
        l.last_rewind_step = -10**9
        req.telemetry = GenerationResult([], [], [], [], [], [], [])
        # first token deferred into the fetch ring: committed at the next
        # drain, before the lane's first decode step is dispatched
        self._push_admit_token(lane, req, logits)
        self.events.append(event)
        if self.ring.depth == 0:
            self._retired_backlog += self._drain_ring()
        return lane

    # ---------------- stepping ---------------- #
    def step_once(self) -> List[Request]:
        """One engine call of the async pipeline: drain the previous
        step's fetch-ring entry (applying its host bookkeeping), then
        dispatch one jitted decode step over all lanes and push its fetch.
        Returns the requests that retired during the drain (their lanes
        are immediately free); with ``async_pipeline=False`` the entry is
        drained in the same call, reproducing the synchronous timing."""
        self.stats.begin_step()
        self._ring_guard()
        finished = self._retired_backlog + self._drain_ring()
        self._retired_backlog = []
        active = [i for i, l in enumerate(self.lanes) if l.request is not None]
        if not active:
            self.stats.cancel_step()
            return finished
        self._note_kv_peak()
        logits, self.state, info = self._step(
            self.params, token=_upload(self.tok),
            pos=_upload(self.pos), step=_upload(self.step),
            state=self.state)
        self.wall_step += 1
        # enqueue per-lane sampling right behind the step, then start the
        # async D2H of tokens + telemetry in ONE ring entry, materialized
        # at the next drain (rewound lanes simply discard their draw)
        keys = ("n_active", "n_frozen", "entropy", "spike", "level",
                "rr_request")
        arrays = dict(
            {k: info[k] for k in keys if k in info},
            toks=self._sample(logits, _upload(self.lane_keys),
                              _upload(self.step), *self._lane_params()))
        offload = self.offloader is not None
        if offload:
            # fold the offload controller's freeze-mask read into the same
            # async fetch (it used to be a second, blocking device pull of
            # the whole token mask every `offload_every` steps), reduced
            # to page granularity ON DEVICE first — page_size x less D2H,
            # and all `sync` ever consumes.  Riding every step lets
            # `needs_sync` gate the expensive cache round-trip instead of
            # a wall-clock cadence, which also makes offload timing a
            # pure function of each lane's own trajectory (async/sync
            # pipeline parity).  The reduction output is a fresh array,
            # so the ring entry never aliases the donated state buffers.
            fz = self.state.freeze.frozen
            pg = self.offloader.page_size
            n_pages = fz.shape[2] // pg
            arrays["frozen_pages"] = fz[:, :, :n_pages * pg].reshape(
                fz.shape[0], fz.shape[1], n_pages, pg).all(axis=-1)
        self.ring.push({"kind": "step", "active": active,
                        "offload": offload,
                        "poison": self._poison_lane(active)}, arrays)
        if self.ring.depth == 0:
            finished += self._drain_ring()
        self.stats.end_step()
        return finished

    def _commit_step(self, meta: Dict[str, Any], host: Dict[str, Any]
                     ) -> List[Request]:
        """Apply one drained step entry: telemetry, rewinds, host offload,
        token commits and retirement — the exact sequence (and order) the
        synchronous path ran inline after its blocking fetch."""
        active = meta["active"]
        get = host.get
        n_active, n_frozen = get("n_active"), get("n_frozen")
        entropy, spike, level = get("entropy"), get("spike"), get("level")
        rr = get("rr_request")
        toks = host["toks"]
        poison = meta.get("poison")
        if poison is not None and entropy is not None:
            # scheduled logits-anomaly injection: the entropy value is the
            # host's only view of the step's logits health, so the poison
            # lands where the corruption would first become visible
            entropy = np.array(entropy, np.float32)
            entropy[poison] = np.nan
        n_layers_attn = max(self.state.freeze.frozen.shape[0], 1)

        # ---- per-lane telemetry: one append per lane-step ----
        for i in active:
            res = self.lanes[i].request.telemetry
            if n_active is not None:
                res.active_kv.append(float(n_active[i]) / n_layers_attn)
                res.frozen_kv.append(float(n_frozen[i]) / n_layers_attn)
            else:
                res.active_kv.append(float(self.pos[i] + 1))
                res.frozen_kv.append(0.0)
            res.total_kv.append(int(self.pos[i]) + 1)
            if entropy is not None:
                res.entropy.append(float(entropy[i]))
                if spike is not None and bool(spike[i]):
                    res.recovery_events.append({
                        "step": int(self.step[i]),
                        "level": int(level[i]),
                        "entropy": float(entropy[i]),
                    })

        # ---- per-lane Rewalk Regeneration ----
        rewound = set()
        if rr is not None:
            for i in active:
                l = self.lanes[i]
                if bool(rr[i]) and len(l.history) >= self.fcfg.rewalk_tokens \
                        and l.rewinds < self.max_rewinds \
                        and int(self.step[i]) - l.last_rewind_step \
                            >= self.rewind_cooldown:
                    self._rewind_bookkeeping(i)
                    rewound.add(i)

        # ---- lane-level anomaly quarantine (non-finite entropy) ----
        quarantined = self._quarantine_scan(active, entropy, rewound)

        # ---- page-batched host offload ----
        if meta["offload"]:
            # admit() drains the ring before scattering a new occupant, so
            # this (page-reduced) mask always predates at most the
            # retirements applied a few lines below — never a re-admission
            frozen = host["frozen_pages"]
            idle = [i for i, l in enumerate(self.lanes)
                    if l.request is None]
            if idle:   # idle lanes decode garbage; never offload it
                frozen = frozen.copy()
                frozen[:, idle, :] = False
            if self.offloader.needs_sync(frozen, reduced=True):
                t0 = time.perf_counter()
                cache = KVCache(k=self.state.cache_k, v=self.state.cache_v)
                cache = self.offloader.sync(cache, frozen, reduced=True)
                self.state = self.state._replace(cache_k=cache.k,
                                                 cache_v=cache.v)
                self.stats.note_blocking(
                    cache.k.nbytes + cache.v.nbytes, d2h=True,
                    seconds=time.perf_counter() - t0)
        for i in active:
            if self.lanes[i].request is None:       # quarantined above
                continue
            self.lanes[i].request.telemetry.offloaded_tokens.append(
                self.offloader.offloaded_tokens_lane(i)
                if self.offloader is not None else 0)
        self._note_stash_peak()

        # ---- commit sampled tokens, retire finished lanes ----
        finished = list(quarantined)
        for i in active:
            if i in rewound:
                continue
            l = self.lanes[i]
            if l.request is None:                   # quarantined above
                continue
            t = int(toks[i])
            l.history.append((t, int(self.pos[i])))
            l.generated.append(t)
            self.tok[i] = t
            self.pos[i] += 1
            self.step[i] += 1
            if len(l.generated) >= l.request.n_tokens:
                finished.append(self._retire(i))
        return finished

    def _retire(self, lane: int) -> Request:
        l = self.lanes[lane]
        req = l.request
        req.result = np.asarray(l.generated[: req.n_tokens], np.int32)
        req.telemetry.tokens = req.result[None, :]
        self._finalize_status(req)
        self.events.append({"event": "finish", "uid": req.uid, "lane": lane,
                            "wall_step": self.wall_step})
        # park the idle lane; the retired request's offloaded pages are
        # released right away (offload sync also masks idle lanes, so no
        # churn until re-admit)
        self._park_lane(lane)
        if self.offloader is not None:
            self.offloader.drop_lane(lane)
        return req

    # ---------------- preemption (suspend / resume) ---------------- #
    def suspend_lane(self, lane: int) -> Optional[LaneSnapshot]:
        """Preempt the lane's request mid-generation and free the lane.

        The contiguous engine has no page-granular stash, so the snapshot
        carries only host bookkeeping (prompt, generated tokens, clocks,
        sampling key); ``resume_lane`` re-prefills prompt + generated —
        cheaper than regenerating but not byte-identical (the freeze /
        recovery state restarts at the resume point; the paged engine's
        stash/restore path is the exact one).  Returns None when the
        request retired while the in-flight fetch drained (its lane is
        already free and the retirement is re-reported by the next
        ``step_once``)."""
        self.flush()
        l = self.lanes[lane]
        if l.request is None:
            return None
        snap = self._snap_host(lane)
        self.events.append({"event": "suspend", "uid": snap.req.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "generated": len(snap.generated)})
        self._park_lane(lane)
        if self.offloader is not None:
            self.offloader.drop_lane(lane)
        return snap

    def resume_lane(self, snap: LaneSnapshot,
                    lane: Optional[int] = None) -> int:
        """Re-admit a suspended request from its snapshot.

        Re-prefills the left-padded prompt plus the already-generated
        tokens (all but the uncommitted input token, whose KV the original
        run had not written yet) into a free lane, then restores the
        host bookkeeping — decode clock, rewind budget and the
        snapshot-stable sampling key — so the continuation draws the same
        sampling stream the uninterrupted run would have.  The re-prefill
        length is re-bucketed to a power of two (extra left-padding,
        exactly like admission's prompt bucketing) so resumes compile
        O(log max_seq) prefill shapes, not one per suspension point; the
        lane's ``pos`` shifts right by the padding, which this approximate
        path tolerates (the paged engine's restore is the exact one)."""
        if not snap.started:
            return self.admit(snap.req, lane)
        self._retired_backlog += self._drain_ring()   # mirror admit's drain
        if lane is None:
            lane = self._free_lane()
        l = self.lanes[lane]
        assert l.request is None, f"lane {lane} is busy"
        prompt = np.asarray(snap.req.prompt, np.int32)
        sp = self._bucket(len(prompt), snap.req.n_tokens)
        assert snap.pos == sp + len(snap.generated) - 1, \
            "snapshot clocks are inconsistent with its token count"
        remaining = snap.req.n_tokens - len(snap.generated) + 1
        sb = self._bucket(snap.pos, remaining)
        toks = np.full((1, sb), self.pad_id, np.int32)
        off = sb - snap.pos                  # re-bucketing pad shift
        toks[0, off + sp - len(prompt):off + sp] = prompt
        toks[0, off + sp:] = snap.generated[:-1]
        lane_state = MD.init_decode_state(self.cfg, 1, self.max_seq)
        self._note_kv_peak(lane_state.cache_k.nbytes
                           + lane_state.cache_v.nbytes)
        _, lane_state = self._prefill(
            self.params, batch={"tokens": _upload(toks)},
            state=lane_state)
        self.state = self._write_lane(self.state, lane_state,
                                      jnp.int32(lane))
        if self.offloader is not None:
            self.offloader.drop_lane(lane)
        self._restore_host(snap, lane)
        self.pos[lane] = sb                  # snap.pos plus the pad shift
        self.events.append({"event": "resume", "uid": snap.req.uid,
                            "lane": lane, "wall_step": self.wall_step})
        return lane


# ===================================================================== #
# Paged continuous batching (bounded-HBM decode + chunked prefill)
# ===================================================================== #
@dataclasses.dataclass
class _PendingPrefill:
    """An admission in flight: the prompt is prefilled chunk-by-chunk into a
    contiguous single-lane scratch cache, interleaved with decode steps of
    the resident lanes; on completion the scratch is repacked into pages
    and installed into the lane.

    ``over=True`` is the preemption variant (``admit_over``): the lane's
    current occupant — the preemption victim — KEEPS DECODING while this
    prefill runs in its scratch, because the scratch never touches the
    lane's page pool.  The victim is suspended only at install time, so a
    preemption costs the victim zero decode opportunity during the
    preemptor's prefill."""
    req: Request
    toks: np.ndarray          # (1, sp) left-padded prompt
    scratch: Any              # contiguous DecodeState (B=1, S=sp)
    sp: int                   # padded prompt length
    done: int = 0             # tokens prefilled so far
    logits: Any = None        # chunk-final logits (valid once done == sp)
    over: bool = False        # preempting the lane's current occupant


class PagedContinuousEngine(_LaneEngineBase):
    """Continuous batching whose decode attends only each lane's bounded
    active page pool: device KV is O(P * page) per lane instead of
    O(max_seq), with frozen / overflow pages living in the host store
    (`core.paging.PagedController`).

    Two serving properties beyond `ContinuousEngine`:

    * **Bounded-HBM decode** — the jitted step (`model.decode_step_paged`,
      Pallas paged-attention kernel on TPU) runs per-lane (B,) pos/step
      clocks and a per-layer, per-lane tail-slot table; page-granular
      freeze plus the forced-freeze bound keep every lane inside its P
      physical slots, and the host controller swaps frozen pages out / due
      pages in at each lane's own page-allocation cadence.

    * **Chunked prefill** — admission prefills the prompt in fixed-size
      chunks (`prefill_chunk` tokens per engine step) into a scratch cache
      while resident lanes keep decoding; the finished prompt is repacked
      into pages (overflow beyond the pool is stashed to the host store)
      and installed with a wholesale per-lane reset
      (`PagedController.write_lane`).  A long prompt therefore never
      head-of-line-blocks the batch.

    * **Async DMA pipeline** (``async_pipeline=True``, the default) — the
      per-step fetch rides the double-buffered ring (module docstring),
      every boundary tick is ONE batched device_get/device_put pair with
      metadata-only pushes when no K/V moved, and ``speculative_slots``
      staging slots per (layer, lane) hold prefetched likely-thaw pages
      (``thaw_urgency`` trend + ``thaw_priority`` ranking) so an FR thaw
      installs as a page-table remap plus a device-side copy instead of a
      blocking upload.  ``async_pipeline=False`` is the same code with a
      depth-0 ring: identical host decisions, and bit-identical tokens
      under a deterministic chunk split (``burst_prefill=False`` — see
      the module docstring; the staging slots are subtracted from the
      jitted step's headroom math, so a P+S pool with S reserved behaves
      exactly like a plain P pool).

    Restricted to attention-only decoder stacks (chunked prefill would
    need cross-chunk recurrent-state threading for mamba/rwkv hybrids).

    **Entropy-guided recovery** (when ``freeze_cfg.recovery_enabled``) runs
    page-granular: the jitted step's ladder (``core.recovery.
    page_recovery_update``) un-freezes *resident* pages in place — they
    re-enter attention through the kernel's per-page visibility mask — and
    raises two host requests the step itself cannot service:

    * ``thaw_request`` (FR level): the lane's stashed host pages are due
      back early.  The engine marks the lane and the ``PagedController``
      thaws at its next page-boundary tick — stashed pages are ranked by
      ``recovery.thaw_priority`` and remapped into free slots, evicting
      the coldest resident page (stashed in turn) once the pool is full.
    * ``rr_request`` (RR level): page-aware Rewalk rewind.  The host
      rewinds ``rewalk_tokens``, invalidates the rewound KV slots on
      device (``model.rewind_paged_lane`` — wholly-rewound pages unmap;
      a rewind landing exactly on a page boundary leaves tail allocation
      to the next boundary tick), makes sure the surviving tail page is
      resident/un-frozen (``PagedController.ensure_resident``), and
      replays from the rewind point.  Budget and cooldown are per lane,
      mirroring ``ContinuousEngine``.
    """

    def __init__(self, cfg: ModelConfig, params,
                 max_seq: Optional[int] = None,
                 n_lanes: Optional[int] = None,
                 max_active_pages: Optional[int] = None,
                 serving: Optional[ServingConfig] = None,
                 **legacy):
        sv = resolve_serving_config(serving, "paged", max_seq, n_lanes,
                                    legacy, max_active_pages=max_active_pages)
        super().__init__(cfg, params, sv)
        quant.resolve_mode(sv.kv_quant)       # fail fast on bad/unsupported
        self.kv_quant = sv.kv_quant
        self.debug_invariants = sv.debug_invariants
        assert sv.max_active_pages >= 3, "pool needs tail + swap headroom"
        assert sv.prefill_chunk >= 1
        max_active_pages = sv.max_active_pages
        self.P = max_active_pages          # usable (allocator-visible) pool
        self.page = self.fcfg.page_size
        self.prefill_chunk = sv.prefill_chunk
        # load-adaptive burst chunks make the chunk split (and with it the
        # flash-attention summation order) depend on engine busyness;
        # disable for runs that must be bit-reproducible across pipelines
        self.burst_prefill = sv.burst_prefill
        self.max_rewinds = sv.max_rewinds
        self.rewind_cooldown = sv.rewind_cooldown
        self.pending_thaws: set = set()   # lanes owed a host thaw (FR level)
        # speculative-thaw staging: S extra physical slots per (layer, lane)
        # hold prefetched stashed pages so a thaw is a page-table remap.
        # The jitted step subtracts them from its headroom math
        # (reserved_slots), so a P+S pool with S reserved is step-for-step
        # identical to a plain P pool — async and sync arms stay
        # token-parity even though only the async arm stages.
        speculative_thaw = sv.speculative_thaw
        if speculative_thaw is None:
            speculative_thaw = sv.async_pipeline
        self.S_stage = sv.speculative_slots if (speculative_thaw
                                                and self.enable_freeze) else 0
        self.P_total = self.P + self.S_stage
        # every program is jitted under a name of its own, so a profiler
        # trace tells them apart (``jit_<name>`` on the device's program
        # line)
        self._step = jax.jit(_named(functools.partial(
            MD.decode_step_paged, cfg=cfg, freeze_cfg=self.fcfg,
            enable_freeze=self.enable_freeze, reserved_slots=self.S_stage),
            "decode_step_paged"), donate_argnames=("state",))
        self._rewind = jax.jit(_named(
            functools.partial(MD.rewind_paged_lane, cfg, page=self.page),
            "rewind_paged_lane"), donate_argnames=("state",))
        self._chunk = jax.jit(
            _named(functools.partial(MD.prefill_chunk, cfg=cfg),
                   "prefill_chunk"), donate_argnames=("state",))
        self._reset_lane = jax.jit(
            _named(functools.partial(MD.reset_paged_lane, cfg),
                   "reset_paged_lane"), donate_argnames=("state",))
        # batched boundary-tick DMA: ONE gather + device_get pulls every
        # boundary lane's pool slice (all layers stacked), ONE scatter +
        # device_put pushes them back.  The lane-index vector is padded to
        # n_lanes (repeating the first lane) so each tuple shape compiles
        # exactly once; duplicate scatter indices write identical columns.
        def gather_lanes(arrs, idx):
            return tuple(jnp.take(a, idx, axis=1) for a in arrs)
        self._gather_lanes = jax.jit(gather_lanes)

        def scatter_lanes(arrs, idx, vals):
            return tuple(a.at[:, idx].set(v.astype(a.dtype))
                         for a, v in zip(arrs, vals))
        self._scatter_lanes = jax.jit(scatter_lanes, donate_argnums=(0,))
        # speculative staging write: scatter one page of K/V per layer into
        # the lane's staging slots (valid=False layers are a no-op)
        def stage_write(state, lane, slots, new_k, new_v, valid):
            li = jnp.arange(state.k.shape[0])
            slots = jnp.maximum(slots, 0)
            sel = valid[:, None, None, None]
            cur_k = state.k[li, lane, slots]
            cur_v = state.v[li, lane, slots]
            k = state.k.at[li, lane, slots].set(
                jnp.where(sel, new_k.astype(state.k.dtype), cur_k))
            v = state.v.at[li, lane, slots].set(
                jnp.where(sel, new_v.astype(state.v.dtype), cur_v))
            return state._replace(k=k, v=v)
        self._stage_write = jax.jit(stage_write, donate_argnames=("state",))
        # staged installs: ONE device-side batched copy staging slots ->
        # target slots per tick (padded to a fixed width so it compiles
        # once; padding rows copy slot 0 onto itself — a no-op)
        def remap_copy(state, layers, lanes, srcs, dsts):
            k = state.k.at[layers, lanes, dsts].set(
                state.k[layers, lanes, srcs])
            v = state.v.at[layers, lanes, dsts].set(
                state.v[layers, lanes, srcs])
            return state._replace(k=k, v=v)
        self._remap_copy = jax.jit(remap_copy, donate_argnames=("state",))
        self._remap_width = 8
        # preemption resume: the pool slice rides _push_lanes, but the
        # recovery ladder is per-lane (B,) state outside the pool fields —
        # restore one lane's scalars with a tiny donated scatter
        def set_recovery(state, lane, ema, level, calm, seen):
            r = state.recovery
            return state._replace(recovery=RecoveryState(
                ema_entropy=r.ema_entropy.at[lane].set(ema),
                level=r.level.at[lane].set(level),
                calm_steps=r.calm_steps.at[lane].set(calm),
                steps_seen=r.steps_seen.at[lane].set(seen)))
        self._set_recovery = jax.jit(set_recovery, donate_argnames=("state",))
        self.state = MD.init_paged_decode_state(
            cfg, self.n_lanes, max_active_pages, staging_slots=self.S_stage)
        self.L_attn = max(self.state.page_table.shape[0], 1)
        assert self.state.page_table.shape[0] == cfg.num_layers, \
            "paged continuous batching requires an attention-only stack"
        self.ctl = PagedController(cfg=cfg, batch=self.n_lanes,
                                   max_active_pages=max_active_pages)
        self.ctl.kv_quant = sv.kv_quant
        self.ctl.stash_budget_bytes = sv.stash_budget_bytes
        if self.injector is not None:
            self.ep_stash = sv.chaos.build_endpoint(
                "stash", self.injector, must_succeed=False)
            self.ctl.stash_endpoint = self.ep_stash
            self._endpoints["stash"] = self.ep_stash
        else:
            self.ep_stash = None
        self.tail_slot = np.zeros((self.L_attn, self.n_lanes), np.int32)
        self.prefills: Dict[int, _PendingPrefill] = {}
        self._urgency = np.zeros(self.n_lanes, np.float32)  # thaw trend/lane
        self.n_boundary_ticks = 0   # boundary maintenance passes (each one
                                    # batched pull + one push)
        self.n_kv_pushes = 0        # pushes that had to carry pool K/V

    @property
    def kv_device_bytes(self) -> int:
        """Live device KV footprint — O(n_lanes * P * page), independent of
        context length (the benchmark's peak-memory metric).  Quantized
        resident pages count at their packed width (1 byte/elem): the CPU
        pool stores the integer-valued payload widened into the pool dtype
        (the kernel dequantizes in place), but on a real TPU the frozen
        region is physically int8/fp8 — the gauge models that layout, so
        the quantized arm's measured reduction is the deployable one."""
        return (self.state.k.nbytes + self.state.v.nbytes
                - self.ctl.device_savings_bytes)

    def _offloaded_tokens_lane(self, lane: int) -> int:
        n = sum(1 for key in self.ctl.frozen_meta if key[1] == lane)
        return n * self.page // self.L_attn

    def _stash_bytes(self) -> int:
        return self.ctl.stash_bytes

    def _exported_bytes(self) -> int:
        return self.ctl.exported_bytes

    def _scratch_bytes(self) -> int:
        return sum(pp.scratch.cache_k.nbytes + pp.scratch.cache_v.nbytes
                   for pp in self.prefills.values())

    # ---------------- device <-> host pool transfer ---------------- #
    # A boundary tick's pool slices cross the host<->device boundary
    # BATCHED: a tick with any number of lanes issues exactly one
    # device_get (a jitted gather over the lane-index vector, padded to
    # n_lanes, stacks all lanes and layers) and one device_put (a donated
    # scatter) — so each moves all n_lanes columns, whatever the number of
    # boundary lanes, and TransferStats counts them all.  Pulled data lands
    # in reused host staging buffers (pinned memory on a real TPU); the
    # push carries K/V only when the controller actually wrote some
    # (kv_dirty) — a tick that only flipped metadata (page-table remaps,
    # freeze counters) moves the metadata fields, not the pool.
    # page_quant / kv_scales travel with BOTH field sets: a metadata-only
    # push (staged-remap tick) must still land the target slots' quant
    # flags + scales — the remap copies the quantized payload device-side,
    # so only the metadata crosses the bus
    _POOL_FIELDS = ("k", "v", "page_table", "slot_mask",
                    "page_quant", "kv_scales")
    _FZ_FIELDS = ("c", "d", "frozen", "frozen_at")
    _META_FIELDS = ("page_table", "slot_mask",
                    "page_quant", "kv_scales") + _FZ_FIELDS

    def _state_arrs(self, fields=None):
        st = self.state
        fields = fields or (self._POOL_FIELDS + self._FZ_FIELDS)
        return tuple(getattr(st, f) if hasattr(st, f)
                     else getattr(st.freeze, f) for f in fields)

    def _padded_idx(self, lanes: List[int]) -> np.ndarray:
        idx = np.full(self.n_lanes, lanes[0], np.int32)
        idx[:len(lanes)] = lanes
        return idx

    def _pull_lanes(self, lanes: List[int]) -> Tuple[dict, dict]:
        m = len(lanes)
        with jax.profiler.TraceAnnotation("repro:engine.pull_lanes",
                                          lanes=m) as span:
            dev = self._gather_lanes(self._state_arrs(),
                                     _upload(self._padded_idx(lanes)))
            t0 = time.perf_counter()
            # the ONE batched D2H for all boundary lanes + layers, recorded
            # in TransferStats below — the pull every per-lane slice rides
            # on.  Under chaos the endpoint fronts it: injected failures
            # burn retries BEFORE device_get runs (must-succeed — the tick
            # cannot proceed without the pool bytes), so the real pull
            # runs once
            if self.ep_pull is not None:
                # hotpath: ok(single batched boundary-tick pull, counted via note_blocking)
                host = self.ep_pull.call(jax.device_get, dev)
            else:
                # hotpath: ok(single batched boundary-tick pull, counted via note_blocking)
                host = jax.device_get(dev)
            dt = time.perf_counter() - t0
            # all n_lanes padded columns crossed the bus, not only the m
            # boundary lanes' ones
            nbytes = sum(a.nbytes for a in host)
            span.set_metadata(bytes=nbytes)
        self.stats.note_blocking(nbytes, d2h=True, seconds=dt)
        names = self._POOL_FIELDS + self._FZ_FIELDS
        with jax.profiler.TraceAnnotation("repro:engine.unpack") as span:
            out = {}
            for name, arr in zip(names, host):
                out[name] = self.staging.put(f"pull_{name}_{m}", arr[:, :m])
            span.set_metadata(bytes=sum(a.nbytes for a in out.values()))
        return ({f: out[f] for f in self._POOL_FIELDS},
                {f: out[f] for f in self._FZ_FIELDS})

    def _push_lanes(self, pool: dict, fstate: dict, lanes: List[int],
                    kv: bool = True) -> None:
        m = len(lanes)
        idx = self._padded_idx(lanes)
        if kv:
            self.n_kv_pushes += 1
        fields = (self._POOL_FIELDS + self._FZ_FIELDS) if kv \
            else self._META_FIELDS
        with jax.profiler.TraceAnnotation("repro:engine.push_lanes", lanes=m,
                                          kv=int(kv)) as span:
            vals = []
            for f in fields:
                src = pool[f] if f in pool else fstate[f]
                buf = self.staging.buf(f"push_{f}",
                                       (src.shape[0], self.n_lanes)
                                       + src.shape[2:], src.dtype)
                buf[:, :m] = src
                if m < self.n_lanes:        # duplicate scatter columns must
                    buf[:, m:] = src[:, :1]  # carry identical data
                vals.append(buf)
            # every padded column crosses the bus
            nbytes = sum(v.nbytes for v in vals)
            span.set_metadata(bytes=nbytes)
            # the dispatch closure runs exactly once per endpoint call —
            # injected failures are simulated before it, never around a
            # half-donated scatter (re-running it would read freed buffers)
            def _dispatch():
                return self._scatter_lanes(self._state_arrs(fields),
                                           _upload(idx),
                                           tuple(_upload(v) for v in vals))
            arrs = self.ep_push.call(_dispatch) if self.ep_push is not None \
                else _dispatch()
        upd = dict(zip(fields, arrs))
        fz = PageFreezeState(*(upd.get(f, getattr(self.state.freeze, f))
                               for f in self._FZ_FIELDS))
        self.state = self.state._replace(
            freeze=fz, **{f: upd[f] for f in self._POOL_FIELDS
                          if f in upd})
        # the K/V of a metadata-only push never crossed the bus: remapped
        # staging slots already hold their page data on device
        if kv:
            self.stats.note_blocking(nbytes, d2h=False)
        else:
            self.stats.note_async(nbytes, d2h=False)

    # ---------------- admission (chunked) ---------------- #
    @property
    def has_free_lane(self) -> bool:
        # a lane mid-over-prefill whose victim already retired holds no
        # request, but its slot is spoken for — never hand it out twice
        return any(l.request is None and i not in self.prefills
                   for i, l in enumerate(self.lanes))

    def _free_lane(self) -> int:
        for i, l in enumerate(self.lanes):
            if l.request is None and i not in self.prefills:
                return i
        raise RuntimeError("no free lane")

    def _queue_prefill(self, req: Request, lane: int,
                       over: bool = False) -> None:
        prompt = np.asarray(req.prompt, np.int32)
        sp = self._bucket(len(prompt), req.n_tokens)
        if not self.enable_freeze:
            # without freezing nothing ever swaps out, so the whole request
            # must fit in the pool (plus the tail-allocation headroom slot)
            need = -(-(sp + req.n_tokens) // self.page) + 1
            if need > self.P:
                raise ValueError(
                    f"request needs ~{need} pages ({sp} prompt + "
                    f"{req.n_tokens} generated tokens) but the pool holds "
                    f"{self.P} and freezing is disabled (no page ever swaps "
                    f"out); enable freezing or raise max_active_pages")
        self.prefills[lane] = _PendingPrefill(
            req=req, toks=self._left_padded(prompt, sp),
            scratch=MD.init_decode_state(self.cfg, 1, sp), sp=sp, over=over)
        self.events.append({"event": "admit_start", "uid": req.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "prompt_len": len(prompt), "bucket": sp,
                            **({"over": True} if over else {})})

    def _assign_lane(self, req: Request, lane: int) -> None:
        l = self.lanes[lane]
        l.request = req
        l.generated = []
        l.history = []
        l.rewinds = 0
        l.last_rewind_step = -10**9
        req.telemetry = GenerationResult([], [], [], [], [], [], [])

    def admit(self, req: Request, lane: Optional[int] = None) -> int:
        """Begin a chunked admission: reserves a lane and queues the prompt
        for chunk-by-chunk prefill.  Returns immediately — resident lanes
        keep decoding while `step_once` advances the prefill."""
        if lane is None:
            lane = self._free_lane()
        l = self.lanes[lane]
        assert l.request is None, f"lane {lane} is busy"
        assert lane not in self.prefills, f"lane {lane} has a prefill queued"
        self._queue_prefill(req, lane)
        self._assign_lane(req, lane)
        return lane

    def admit_over(self, req: Request, lane: int) -> int:
        """Preempting admission: queue `req`'s chunked prefill against a
        lane whose current occupant keeps decoding.  The prefill runs in a
        scratch cache that never touches the lane's page pool, so the
        victim loses nothing while the preemptor's prompt is processed; at
        install time the victim is suspended (``suspend_lane`` semantics —
        full stash/restore snapshot, surfaced via ``drain_suspended``) and
        the preemptor takes the lane.  This is what makes preemption
        throughput-neutral: the only lane-time the victim ever gives up is
        time the preemptor is actually decoding.  If the victim retires
        before the prefill completes, the install degenerates to a normal
        admission and no snapshot is produced."""
        l = self.lanes[lane]
        assert l.request is not None, \
            f"lane {lane} is free — use admit(), not admit_over()"
        assert lane not in self.prefills, \
            f"lane {lane} already has a prefill queued"
        self._queue_prefill(req, lane, over=True)
        return lane

    def _chunk_sizes(self, sp: int) -> List[int]:
        """Every chunk length a prompt bucket `sp` can hit, over all
        interleaved/burst schedules (small closed set: the schedule only
        ever picks min(prefill_chunk, rem) or the largest power-of-two
        multiple of it that fits rem)."""
        sizes, seen, frontier = set(), set(), {sp}
        while frontier:
            rem = frontier.pop()
            if rem <= 0 or rem in seen:
                continue
            seen.add(rem)
            ci = min(self.prefill_chunk, rem)
            cb = self.prefill_chunk
            while cb * 2 <= rem:
                cb *= 2
            cb = min(cb, rem)
            sizes.update((ci, cb))
            frontier.update((rem - ci, rem - cb))
        return sorted(sizes)

    def warm_prefill(self, prompt_len: int, n_tokens: int) -> None:
        """Pre-compile every prefill-chunk shape a prompt of this length
        can encounter (the burst schedule makes the shape sequence depend
        on engine load, so production warmup must cover the closed set,
        not one observed trace)."""
        sp = self._bucket(prompt_len, n_tokens)
        state = MD.init_decode_state(self.cfg, 1, sp)
        for c in self._chunk_sizes(sp):
            _, state = self._chunk(self.params,
                                   tokens=jnp.zeros((1, c), jnp.int32),
                                   state=state, pos0=jnp.int32(0))

    def _prefill_tick(self, lane: int, busy: bool = True) -> None:
        """Advance one admission by one prompt chunk.

        `busy=False` (no resident lane is decoding) grows the chunk to the
        largest power of two that fits the remainder: fine-grained chunks
        only buy anything when there is decode work to interleave, so an
        empty engine admits at near-whole-prefill speed while a busy one
        keeps the configured interleave granularity.  Chunk lengths stay
        powers of two, so compiles remain O(log max_seq)."""
        pp = self.prefills[lane]
        self._note_kv_peak(self._scratch_bytes())
        rem = pp.sp - pp.done
        c = self.prefill_chunk
        if not busy and self.burst_prefill:
            while c * 2 <= rem:
                c *= 2
        c = min(c, rem)
        with jax.profiler.TraceAnnotation("repro:engine.prefill",
                                          uid=pp.req.uid, tokens=c):
            chunk = _upload(pp.toks[:, pp.done:pp.done + c])
            pp.logits, pp.scratch = self._chunk(
                self.params, tokens=chunk, state=pp.scratch,
                pos0=jnp.int32(pp.done))
        pp.done += c
        self.events.append({"event": "prefill_chunk", "uid": pp.req.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "done": pp.done, "total": pp.sp})
        if pp.done >= pp.sp:
            with jax.profiler.TraceAnnotation(
                    "repro:engine.install", uid=pp.req.uid,
                    pages=-(-pp.sp // self.page)):
                self._install(lane)

    def _install(self, lane: int) -> None:
        """Repack the finished scratch prefill into pages and install them
        into the lane: the newest pages fill the device pool, older pages
        are stashed in the host store (returning as slots free up), and
        `PagedController.write_lane` wholesale-resets exactly this lane."""
        pp = self.prefills.pop(lane)
        if pp.over:
            # install-time preemption: the victim decoded right through the
            # preemptor's prefill; suspend it now (full stash/restore
            # snapshot, picked up via drain_suspended) — unless it already
            # retired, in which case this is a normal install
            if self.lanes[lane].request is not None:
                snap = self._suspend_decode(lane)
                if snap is not None:
                    self._suspended.append(snap)
            self._assign_lane(pp.req, lane)
        sp, page, P, L = pp.sp, self.page, self.P, self.L_attn
        P_total = self.P_total
        # wholesale lane reset first: beyond the pool fields the push below
        # overwrites, this clears the lane's recovery ladder — the decode
        # steps that ran while this admission was in flight advanced the
        # lane's entropy baseline on garbage logits, which must not leak
        # into the new occupant
        self.state = self._reset_lane(state=self.state, lane=jnp.int32(lane))
        # (L, sp, KVH, hd) host repack: one pull per finished prefill (not
        # per step) to slice the scratch cache into pool pages
        # hotpath: ok(once-per-admission install repack, amortized over the request)
        ck = np.array(pp.scratch.cache_k[:, 0])
        # hotpath: ok(once-per-admission install repack, amortized over the request)
        cv = np.array(pp.scratch.cache_v[:, 0])
        n_pages = -(-sp // page)
        pad = n_pages * page - sp
        if pad:
            ck = np.pad(ck, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cv = np.pad(cv, ((0, 0), (0, pad), (0, 0), (0, 0)))
        ck = ck.reshape(L, n_pages, page, *ck.shape[2:])
        cv = cv.reshape(L, n_pages, page, *cv.shape[2:])
        masks = (np.arange(n_pages * page) < sp).reshape(n_pages, page)
        # newest pages resident (leave one slot free for the next tail);
        # older prompt pages overflow to the host store and cycle back in
        # as the freeze schedule frees slots
        r = min(n_pages, P - 1)
        # write_lane overwrites every byte of the lane slice, so build it
        # host-side instead of pulling the stale device copy first
        kvh, hd = ck.shape[-2:]
        dt = np.dtype(self.state.k.dtype)
        pool = {"k": np.zeros((L, 1, P_total, page, kvh, hd), dt),
                "v": np.zeros((L, 1, P_total, page, kvh, hd), dt),
                "page_table": np.full((L, 1, P_total), -1, np.int32),
                "slot_mask": np.zeros((L, 1, P_total, page), bool),
                "page_quant": np.zeros((L, 1, P_total), np.int32),
                "kv_scales": np.ones((L, 1, P_total, 2, kvh), np.float32)}
        fstate = {"c": np.zeros((L, 1, P_total), np.int32),
                  "d": np.zeros((L, 1, P_total), np.int32),
                  "frozen": np.zeros((L, 1, P_total), bool),
                  "frozen_at": np.zeros((L, 1, P_total), np.int32)}
        # write_lane drops the lane's host store, so overflow pages must be
        # stashed AFTER it or they'd be deleted before decode ever starts
        self.ctl.write_lane(pool, fstate, 0,
                            ck[:, n_pages - r:], cv[:, n_pages - r:],
                            np.arange(n_pages - r, n_pages, dtype=np.int32),
                            masks[n_pages - r:], store_lane=lane)
        # overflow pages are not low-relevance, just oldest-out: timer 1
        # returns each the moment the freeze schedule frees a slot
        for gp in range(n_pages - r):
            for layer in range(L):
                self.ctl.stash(layer, lane, gp, ck[layer, gp], cv[layer, gp],
                               d=1)
        # the last S_stage physical slots start out as the lane's staging
        # slots (write_lane only ever fills slots 0..P-1); drop_lane inside
        # write_lane already forgot any staged keys of the lane's previous
        # occupant
        for layer in range(L):
            self.ctl.stage_slots[(layer, lane)] = \
                list(range(self.P, P_total))
        self._push_lanes(pool, fstate, [lane])
        if sp % page:                       # partial tail page is resident
            self.tail_slot[:, lane] = r - 1
        self.pos[lane] = sp                 # sp % page == 0 -> the boundary
        self.step[lane] = 0                 # alloc runs before the next step
        # first token deferred into the fetch ring: sampling stays on
        # device behind the last prefill chunk; the host commits the
        # value at the next drain, before the first decode dispatch
        self._push_admit_token(lane, pp.req, pp.logits)
        self.events.append({"event": "admit", "uid": pp.req.uid,
                            "lane": lane, "wall_step": self.wall_step})

    # ---------------- stepping ---------------- #
    def _keep_gids(self, lane: int) -> Tuple[int, ...]:
        """Global page ids the host must never evict for this lane: the
        tail page plus the freeze window (the jitted step would just
        re-write / re-attend them)."""
        cp = int(self.pos[lane]) // self.page
        window_pages = max(1, -(-self.fcfg.window // self.page))
        return tuple(range(max(0, cp - window_pages), cp + 1))

    def step_once(self) -> List[Request]:
        """One engine call of the async pipeline: drain the previous
        step's fetch-ring entry (telemetry, thaw requests, page rewinds,
        token commits, retirement), then per-lane page-boundary
        maintenance (ONE batched pull, host swap tick, pending thaws, tail
        allocation, ONE batched push — metadata-only if no K/V moved), a
        jitted paged decode step over the resident lanes with its fetch
        pushed asynchronously behind it, speculative thaw staging, and one
        prefill chunk for every admission in flight.  Returns retired
        requests (from the drain; same-call with ``async_pipeline=False``).

        In a profiler capture the call is the ``repro:engine.step`` span
        (``wall_step``, decode ``lanes``, ``boundary`` lanes), with the
        spans of docs/serving.md ("Spans in a profiler capture") inside."""
        with jax.profiler.TraceAnnotation("repro:engine.step",
                                          wall_step=self.wall_step) as span:
            self.stats.begin_step()
            self._ring_guard()
            finished = self._retired_backlog + self._drain_ring()
            self._retired_backlog = []
            decode_lanes = [i for i, l in enumerate(self.lanes)
                            if l.request is not None
                            and (i not in self.prefills
                                 or self.prefills[i].over)]
            boundary = [i for i in decode_lanes
                        if self.pos[i] % self.page == 0]
            span.set_metadata(lanes=len(decode_lanes),
                              boundary=len(boundary))
            if decode_lanes:
                if boundary:
                    self._boundary_tick(boundary)
                with jax.profiler.TraceAnnotation("repro:engine.decode"):
                    live = np.zeros(self.n_lanes, bool)
                    live[decode_lanes] = True
                    self._note_kv_peak(self._scratch_bytes())
                    logits, self.state, info = self._step(
                        self.params, token=_upload(self.tok),
                        pos=_upload(self.pos), step=_upload(self.step),
                        tail_slot=_upload(self.tail_slot), state=self.state,
                        live=_upload(live))
                    self.wall_step += 1
                    keys = ("n_active_slots_lane", "n_frozen_pages_lane",
                            "entropy", "spike", "level", "ema_entropy",
                            "rr_request", "thaw_request")
                    arrays = dict(
                        {k: info[k] for k in keys if k in info},
                        toks=self._sample(logits, _upload(self.lane_keys),
                                          _upload(self.step),
                                          *self._lane_params()))
                    self.ring.push({"kind": "step",
                                    "active": list(decode_lanes),
                                    "poison": self._poison_lane(decode_lanes)},
                                   arrays)
                # start copying likely-thaw pages into the staging slots
                # while the step computes — by the time an FR thaw fires at
                # a boundary tick, its pages install as a page-table remap
                self._maybe_prefetch(decode_lanes)

            # ---- chunked prefill: one chunk per admission in flight ---- #
            for lane in list(self.prefills):
                self._prefill_tick(lane, busy=bool(decode_lanes))
            if self.ring.depth == 0:
                finished += self._drain_ring()
            if decode_lanes:
                self.stats.end_step()
            else:
                self.stats.cancel_step()
            return finished

    def _boundary_tick(self, boundary: List[int]) -> None:
        """Page-boundary maintenance for `boundary` lanes: one batched
        pull, the host controller pass (timer swaps, pending thaws, tail
        allocation with the force-free backstop), one batched push, then
        the queued device-side staging remaps.  Spans: ``repro:engine.tick``
        around it all, ``repro:kv.tick`` around the controller pass."""
        with jax.profiler.TraceAnnotation("repro:engine.tick",
                                          lanes=len(boundary)):
            self.n_boundary_ticks += 1
            pool, fstate = self._pull_lanes(boundary)
            with jax.profiler.TraceAnnotation("repro:kv.tick"):
                self._controller_pass(boundary, pool, fstate)
            if self.debug_invariants:
                # the one moment the host holds a coherent cross-structure
                # view: post-controller-pass, pre-push
                from repro.analysis import audit_boundary
                audit_boundary(self.ctl, pool, fstate, range(len(boundary)),
                               lane_ids={bi: i
                                         for bi, i in enumerate(boundary)})
            self._note_stash_peak()
            self._push_lanes(pool, fstate, boundary, kv=self.ctl.kv_dirty)
            self._run_remaps()

    def _controller_pass(self, boundary: List[int], pool: dict,
                         fstate: dict) -> None:
        """The host controller's share of a boundary tick, on the pulled
        slices: timer swaps, pending thaws, and tail allocation with the
        force-free backstop."""
        # graceful-degradation ladder, engine-applied rungs: under stash
        # pressure first reclaim redundant host copies of resident pages
        # (stage 1+, parity-free), then deepen the forced-freeze timers so
        # stashed pages return to the device half as fast (stage 2+) —
        # stages 3/4 (admission throttle, lane shed) belong to the
        # scheduler, which reads ``stash_pressure``
        pressure = self.stash_pressure
        if pressure >= self.ladder_cfg.deny_prefetch:
            self.ctl.trim_resident_copies()
        self.ctl.deepen_timers = pressure >= self.ladder_cfg.deepen_timers
        if self.ctl.deepen_timers:
            self.robust["ladder_deepen"] += 1
        self.ctl.begin_tick()
        self._prune_staged()
        keep = {bi: self._keep_gids(i) for bi, i in enumerate(boundary)}
        thaw = tuple(bi for bi, i in enumerate(boundary)
                     if i in self.pending_thaws)
        self.ctl.tick(pool, fstate, step=self.wall_step,
                      lane_ids=tuple(boundary),
                      thaw_lanes=thaw, keep_gids=keep)
        self.pending_thaws -= set(boundary)
        for bi, i in enumerate(boundary):
            slots = self.ctl.alloc_tail_lane(
                pool, bi, int(self.pos[i]) // self.page, lane_id=i)
            if slots is None and self.enable_freeze:
                # recovery may have un-frozen every page the timer
                # pass would have swapped out; the host is the
                # bound's enforcer of last resort — stash the
                # coldest page and retry
                self.ctl.force_free_slot(pool, fstate, bi, i,
                                         keep_gids=keep[bi])
                slots = self.ctl.alloc_tail_lane(
                    pool, bi, int(self.pos[i]) // self.page, lane_id=i)
            if slots is None:
                raise RuntimeError(
                    f"lane {i}: page pool exhausted"
                    + (" (forced freeze should have kept headroom)"
                       if self.enable_freeze else
                       " — freezing is disabled, so nothing swaps "
                       "out; admission should have rejected this"))
            self.tail_slot[:, i] = slots

    def _commit_step(self, meta: Dict[str, Any], host: Dict[str, Any]
                     ) -> List[Request]:
        """Apply one drained paged-step entry — the exact sequence (and
        order) the synchronous path ran inline after its blocking fetch:
        telemetry, thaw requests, page-aware rewinds, token commits,
        retirement."""
        decode_lanes = meta["active"]
        get = host.get
        toks = host["toks"]
        act, fro = get("n_active_slots_lane"), get("n_frozen_pages_lane")
        entropy, spike, level = get("entropy"), get("spike"), get("level")
        rr, thaw_req = get("rr_request"), get("thaw_request")
        poison = meta.get("poison")
        if poison is not None and entropy is not None:
            # scheduled logits-anomaly injection (host-side: entropy is
            # computed inside the jitted step, so the commit is where the
            # corrupt value first becomes visible to the host)
            entropy = np.array(entropy, np.float32)
            entropy[poison] = np.nan

        for i in decode_lanes:
            res = self.lanes[i].request.telemetry
            if act is not None:
                res.active_kv.append(float(act[i]) / self.L_attn)
                res.frozen_kv.append(
                    float(fro[i]) * self.page / self.L_attn)
            else:
                res.active_kv.append(float(self.pos[i] + 1))
                res.frozen_kv.append(0.0)
            res.total_kv.append(int(self.pos[i]) + 1)
            res.offloaded_tokens.append(self._offloaded_tokens_lane(i))
            if entropy is not None:
                res.entropy.append(float(entropy[i]))
                if spike is not None and bool(spike[i]):
                    res.recovery_events.append({
                        "step": int(self.step[i]),
                        "level": int(level[i]),
                        "entropy": float(entropy[i]),
                    })
        # thaw-urgency trend for the speculative prefetcher (only the
        # escalation level and the entropy-vs-baseline ratio matter, both
        # of which ride the same ring entry)
        if entropy is not None and get("ema_entropy") is not None:
            from repro.core.recovery import thaw_urgency
            urg = thaw_urgency(level, entropy, get("ema_entropy"))
            for i in decode_lanes:
                self._urgency[i] = urg[i]

        # ---- recovery servicing: host thaws + page-aware rewinds ----
        if thaw_req is not None:
            for i in decode_lanes:
                if bool(thaw_req[i]):
                    # serviced by PagedController.thaw_lane at the
                    # lane's next page-boundary tick
                    self.pending_thaws.add(i)
        rewound = set()
        if rr is not None:
            for i in decode_lanes:
                l = self.lanes[i]
                if bool(rr[i]) and len(l.history) >= self.fcfg.rewalk_tokens \
                        and l.rewinds < self.max_rewinds \
                        and int(self.step[i]) - l.last_rewind_step \
                            >= self.rewind_cooldown \
                        and self._rewind_lane(i):
                    rewound.add(i)

        # ---- lane-level anomaly quarantine (non-finite entropy) ----
        quarantined = self._quarantine_scan(decode_lanes, entropy, rewound)

        finished = list(quarantined)
        for i in decode_lanes:
            if i in rewound:
                continue
            l = self.lanes[i]
            if l.request is None:               # quarantined above
                continue
            t = int(toks[i])
            l.history.append((t, int(self.pos[i])))
            l.generated.append(t)
            self.tok[i] = t
            self.pos[i] += 1
            self.step[i] += 1
            if len(l.generated) >= l.request.n_tokens:
                finished.append(self._retire(i))
        return finished

    # ---------------- speculative thaw staging ---------------- #
    def _prune_staged(self) -> None:
        """Forget staged copies whose host page vanished (rewind drop,
        lane reset) — their staging slots become available again."""
        stale = [k for k in self.ctl.staged_keys
                 if k not in self.ctl.frozen_meta]
        for k in stale:
            del self.ctl.staged_keys[k]

    def _run_remaps(self) -> None:
        """Execute the controller's queued staging-slot remaps as ONE
        batched device-side page copy (staging slot -> the install's
        target slot).  Nothing crosses the host<->device boundary — this
        is what makes a staged thaw "remap-only" — and the consumed
        staging slots are immediately reusable for the next prefetch."""
        remaps = self.ctl.pending_remaps
        self.ctl.pending_remaps = []
        W = self._remap_width
        with jax.profiler.TraceAnnotation("repro:engine.remap",
                                          n=len(remaps)):
            for i in range(0, len(remaps), W):
                chunk = remaps[i:i + W]
                ls, lanes = np.zeros(W, np.int32), np.zeros(W, np.int32)
                # padding rows self-copy a staging slot — never a real
                # remap's destination, so the batched scatter stays
                # conflict-free
                srcs = np.full(W, self.P, np.int32)
                dsts = np.full(W, self.P, np.int32)
                for j, (l, lane, src, dst) in enumerate(chunk):
                    ls[j], lanes[j], srcs[j], dsts[j] = l, lane, src, dst
                self.state = self._remap_copy(
                    self.state, _upload(ls), _upload(lanes),
                    _upload(srcs), _upload(dsts))

    def _maybe_prefetch(self, decode_lanes: List[int]) -> None:
        """Dispatch speculative staging uploads for lanes trending toward
        an FR thaw: the highest-urgency lane's top thaw candidates (by
        ``recovery.thaw_priority`` — the exact ranking ``thaw_lane`` will
        use) are copied into its staging slots.  Budget: at most
        ``S_stage`` staged *pages* (gids) per step; each is ONE batched
        dispatch carrying that page's K/V for every attention layer that
        has it stashed, i.e. up to ``S_stage * L_attn`` page-sized
        uploads per step on a deep stack.  The H2D copies are dispatched
        asynchronously behind the decode step; they never change page
        tables, so a misprediction costs bandwidth, not correctness."""
        with jax.profiler.TraceAnnotation("repro:engine.prefetch"):
            if not self.S_stage:
                return
            if self.stash_pressure >= self.ladder_cfg.deny_prefetch:
                # ladder stage 1: deny speculative prefetch under stash
                # pressure (staging is pure optimization — thaws fall back to
                # the sync upload path, token-identically)
                self.robust["ladder_deny"] += 1
                return
            if self.ep_stage is not None and not self.ep_stage.allow():
                # tripped stage breaker: speculative staging stays disabled
                # until the breaker's op-count cooldown re-closes it (same
                # token-identical sync-upload fallback)
                return
            # stage for lanes that WILL thaw (request pending, boundary tick
            # not yet reached) and for lanes trending within one spike of FR
            # (urgency >= WR) — looser gating buys little and costs a state
            # dispatch per staged page
            from repro.core.recovery import WR
            cands = [i for i in decode_lanes
                     if i in self.pending_thaws or self._urgency[i] >= WR]
            cands.sort(key=lambda i: (i not in self.pending_thaws,
                                      -self._urgency[i]))
            budget = self.S_stage
            for lane in cands:
                while budget and self._prefetch_lane(lane):
                    budget -= 1
                if not budget:
                    return

    def _prefetch_lane(self, lane: int) -> bool:
        from repro.core.recovery import thaw_priority
        metas = [(key, m) for key, m in self.ctl.frozen_meta.items()
                 if key[1] == lane]
        if not metas:
            return False
        gid_score: Dict[int, float] = {}
        for (l, _, gid), m in metas:
            s = thaw_priority(m["c"], m["frozen_at"])
            gid_score[gid] = max(gid_score.get(gid, -np.inf), s)
        staged_gids = {k[2] for k in self.ctl.staged_keys if k[1] == lane}
        occupied = {}
        for k, slot in self.ctl.staged_keys.items():
            if k[1] == lane:
                occupied.setdefault(k[0], set()).add(slot)
        # canonical tie-break (gid) mirrors thaw_lane's: the staging
        # schedule must be invariant to frozen_meta insertion order, which
        # a suspend/resume migration rebuilds
        want = sorted(gid_score,
                      key=lambda g: (-gid_score[g], g))[:self.S_stage]
        page, kvh, hd = self.state.k.shape[3:]
        for gid in want:
            if gid in staged_gids:
                continue
            slots = np.full(self.L_attn, -1, np.int32)
            valid = np.zeros(self.L_attn, bool)
            k_buf = self.staging.buf("stage_k",
                                     (self.L_attn, page, kvh, hd),
                                     np.dtype(self.state.k.dtype))
            v_buf = self.staging.buf("stage_v",
                                     (self.L_attn, page, kvh, hd),
                                     np.dtype(self.state.v.dtype))
            sent = 0
            for l in range(self.L_attn):
                key = (l, lane, gid)
                if key not in self.ctl.frozen_meta:
                    continue
                avail = [s for s in self.ctl.stage_slots.get((l, lane), [])
                         if s not in occupied.get(l, ())]
                if not avail:
                    continue
                # a quantized store entry is a 1-byte payload; assigning it
                # into the pool-dtype buffer widens the integer values
                # exactly (the kernel dequantizes once the page is mapped,
                # scales riding the metadata push)
                kk, vv = self.ctl.store[key]
                k_buf[l] = kk
                v_buf[l] = vv
                sent += kk.nbytes + vv.nbytes
                slots[l] = avail[0]
                valid[l] = True
            if not valid.any():
                continue
            # the dispatch closure runs exactly once per endpoint call
            # (injection precedes it); a best-effort failure returns
            # FAILED with the state untouched — the thaw just won't be
            # staged, and installs fall back to the sync upload path
            def _dispatch():
                return self._stage_write(
                    self.state, jnp.int32(lane), _upload(slots),
                    _upload(k_buf), _upload(v_buf),
                    _upload(valid))
            if self.ep_stage is not None:
                out = self.ep_stage.call(_dispatch)
                if out is Endpoint.FAILED:
                    return False
                self.state = out
            else:
                self.state = _dispatch()
            for l in range(self.L_attn):
                if valid[l]:
                    self.ctl.staged_keys[(l, lane, gid)] = int(slots[l])
            # the whole pool-dtype buffers cross the bus, every layer's
            # page whether it was stashed or not
            self.stats.note_async(k_buf.nbytes + v_buf.nbytes, d2h=False)
            return True
        return False

    def _rewind_lane(self, lane: int) -> bool:
        """Rewalk Regeneration on the paged path: rewind ``rewalk_tokens``,
        invalidate the rewound KV slots on device, and make the surviving
        tail page attendable again.  Pages wholly past the rewind point
        unmap (a boundary-landing rewind leaves tail re-allocation to the
        next page-boundary tick) and their stale host copies are dropped —
        the replayed pages must never collide with a stashed copy of the
        rewound generation.  Returns False (rewind skipped, nothing
        mutated) if the tail page cannot be made resident.

        The in-flight fetch (async pipeline) is consumed first: its commit
        carries a token for the PRE-rewind position, and applying it after
        the surgery below would clobber the rewound clocks and replay
        token.  Draining makes the host bookkeeping current at the
        injection point in both pipeline modes (re-entrant calls from
        ``_commit_step``'s RR path see an already-empty ring — no-op)."""
        self._retired_backlog += self._drain_ring()
        l = self.lanes[lane]
        if l.request is None:        # the drained commit retired this lane
            return False
        nback = self.fcfg.rewalk_tokens
        new_pos = int(self.pos[lane]) - nback
        if new_pos <= 0:
            return False
        gid_t = new_pos // self.page
        window_pages = max(1, -(-self.fcfg.window // self.page))
        keep = tuple(range(max(0, gid_t - window_pages), gid_t + 1))
        if new_pos % self.page:
            # mid-page landing: the tail page must be resident + un-frozen
            # in every layer before decode resumes (it may have been
            # frozen or even stashed if the freeze window is one page)
            self.ctl.begin_tick()
            self._prune_staged()
            pool, fstate = self._pull_lanes([lane])
            ok = self.ctl.ensure_resident(pool, fstate, 0, lane, gid_t,
                                          keep_gids=keep)
            # push back even on failure: a partial layer's thaw/eviction
            # mutated both the pulled copies and the controller's host
            # bookkeeping, and dropping the copies would desynchronize
            # them (duplicate swap-ins / unreachable host pages)
            self._push_lanes(pool, fstate, [lane], kv=self.ctl.kv_dirty)
            self._run_remaps()
            if not ok:
                return False
            for lyr in range(self.L_attn):
                slot = np.nonzero(pool["page_table"][lyr, 0] == gid_t)[0]
                self.tail_slot[lyr, lane] = int(slot[0])
        self.state = self._rewind(state=self.state, lane=jnp.int32(lane),
                                  new_pos=jnp.int32(new_pos))
        self.ctl.drop_pages_from(lane, -(-new_pos // self.page))
        self._rewind_bookkeeping(lane)
        self.events.append({"event": "rewind", "uid": l.request.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "new_pos": new_pos})
        return True

    def _quarantine_rewind(self, lane: int) -> bool:
        return self._rewind_lane(lane)

    # ---------------- preemption (suspend / resume) ---------------- #
    def suspend_lane(self, lane: int) -> Optional[LaneSnapshot]:
        """Freeze-native preemption: force-stash the lane's entire device
        residency and free the lane without losing any decode progress.

        The snapshot owns (1) the lane's full pool slice — K/V pages,
        page table, slot masks and page-freeze counters, pulled in the
        same ONE batched transfer a boundary tick uses — (2) the lane's
        recovery-ladder scalars, and (3) every host-stashed page, *moved
        out of* the ``PagedController`` store (``export_lane``) so
        reassigning the lane cannot ``drop_lane`` them.  ``resume_lane``
        pushes the slice back verbatim (possibly into a different lane),
        so the continuation is **token-identical** to the uninterrupted
        run — preemption costs two pool-slice transfers, never a
        re-prefill.

        An admission still mid-chunked-prefill is cancelled instead (no
        decode progress exists yet): the snapshot re-admits from scratch.
        On a lane mid-``admit_over`` this suspends the decoding VICTIM and
        leaves the preemptor's prefill queued (it then installs into the
        freed lane as a normal admission).  Returns None when the request
        retired while the in-flight fetch drained (the retirement is
        re-reported by the next ``step_once``)."""
        self.flush()
        l = self.lanes[lane]
        pp = self.prefills.get(lane)
        if pp is not None and not pp.over:
            if l.request is None:
                return None
            self.prefills.pop(lane)
            snap = LaneSnapshot(req=pp.req, generated=[], history=[],
                                pos=0, step=0, tok=self.pad_id,
                                rewinds=0, last_rewind_step=-10**9)
            self.events.append({"event": "suspend", "uid": pp.req.uid,
                                "lane": lane, "wall_step": self.wall_step,
                                "generated": 0})
            self.ctl.drop_lane(lane)
            self._park_lane(lane)
            return snap
        return self._suspend_decode(lane)

    def _suspend_decode(self, lane: int) -> Optional[LaneSnapshot]:
        """The decode-lane suspension core shared by ``suspend_lane`` and
        the install-time preemption of ``admit_over``: flush, snapshot,
        stash, free."""
        self.flush()
        l = self.lanes[lane]
        if l.request is None:
            return None
        snap = self._snap_host(lane)
        # speculative staged copies survive the lane changing hands: the
        # pulled pool slice spans all P_total slots (staging included) and
        # every lane reserves the same [P, P_total) staging range, so the
        # slice push restores the bytes verbatim on any destination lane.
        # The slot bookkeeping rides the export (4th tuple element) —
        # dropping it here is what used to break ≥4-cycle parity under
        # recovery: a forgotten staged page de-scheduled the resumed
        # lane's thaw remap, and the timing shift fed an
        # entropy-triggered Rewalk a different path
        # (docs/robustness.md parity envelope)
        pool, fstate = self._pull_lanes([lane])
        # deep-copy out of the reused staging buffers — the next pull
        # overwrites them, the snapshot may outlive many ticks
        snap.pool = {f: a.copy() for f, a in pool.items()}
        snap.fstate = {f: a.copy() for f, a in fstate.items()}
        rec = jax.device_get(self.state.recovery)
        snap.recovery = {f: np.asarray(a)[lane].item()
                         for f, a in zip(RecoveryState._fields, rec)}
        snap.tail_slot = self.tail_slot[:, lane].copy()
        snap.stashed = self.ctl.export_lane(lane)
        snap.pending_thaw = lane in self.pending_thaws
        snap.urgency = float(self._urgency[lane])
        self.events.append({"event": "suspend", "uid": snap.req.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "generated": len(snap.generated),
                            "stashed_pages": len(snap.stashed)})
        # free the lane: unmap on device, clear host bookkeeping
        self.state = self._reset_lane(state=self.state, lane=jnp.int32(lane))
        self.ctl.drop_lane(lane)
        self.pending_thaws.discard(lane)
        self._urgency[lane] = 0.0
        self._park_lane(lane)
        return snap

    def resume_lane(self, snap: LaneSnapshot,
                    lane: Optional[int] = None) -> int:
        """Re-admit a suspended request via the stash/restore path — no
        re-prefill.  The snapshot's host-store pages are rekeyed to the
        destination lane (``import_lane``), its pool slice is pushed back
        byte-identical (same physical slot layout → same float summation
        order downstream → token parity with the uninterrupted run), and
        the recovery-ladder scalars, tail slots, clocks and the
        snapshot-stable sampling key are restored."""
        if not snap.started:
            return self.admit(snap.req, lane)
        self._retired_backlog += self._drain_ring()
        if lane is None:
            lane = self._free_lane()
        l = self.lanes[lane]
        assert l.request is None, f"lane {lane} is busy"
        assert lane not in self.prefills, f"lane {lane} has a prefill queued"
        # host store first: thaw/swap bookkeeping must see the pages the
        # pushed page table expects to find stashed.  A checkpoint
        # snapshot's bytes were never moved out of the controller's
        # accounting, so nothing moves back (counted=False)
        self.ctl.import_lane(lane, snap.stashed, counted=snap.exported)
        self._push_lanes(snap.pool, snap.fstate, [lane])
        # the snapshot's pool slice may carry quantized resident pages —
        # rebuild the destination lane's packed-residency ledger
        self.ctl.refresh_resident_quant(snap.pool, 0, lane)
        for lyr in range(self.L_attn):
            self.ctl.stage_slots[(lyr, lane)] = \
                list(range(self.P, self.P_total))
        r = snap.recovery
        self.state = self._set_recovery(
            self.state, jnp.int32(lane),
            jnp.float32(r["ema_entropy"]), jnp.int32(r["level"]),
            jnp.int32(r["calm_steps"]), jnp.int32(r["steps_seen"]))
        self.tail_slot[:, lane] = snap.tail_slot
        self._restore_host(snap, lane)
        if snap.pending_thaw:
            self.pending_thaws.add(lane)
        self._urgency[lane] = snap.urgency
        self.events.append({"event": "resume", "uid": snap.req.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "stashed_pages": len(snap.stashed)})
        return lane

    def cancel_request(self, uid: int) -> Optional[Request]:
        """Paged cancellation also reaches a preemptor still running its
        ``admit_over`` chunked prefill: the prefill's scratch cache never
        touched the lane's page pool, so dropping the pending prefill is
        the whole cancellation — the victim keeps decoding, undisturbed."""
        for lane, pp in list(self.prefills.items()):
            if pp.req.uid == uid and pp.over:
                self.prefills.pop(lane)
                req = pp.req
                req.status = RequestStatus.CANCELLED
                req.result = np.zeros(0, np.int32)
                self.events.append({"event": "cancel", "uid": uid,
                                    "lane": lane,
                                    "wall_step": self.wall_step,
                                    "generated": 0})
                return req
        return super().cancel_request(uid)

    def discard_snapshot(self, snap: LaneSnapshot) -> None:
        """A suspended paged request that will never resume still owns
        its exported host-stash pages (``export_lane`` moved them OUT of
        the controller store precisely so lane reuse could not drop
        them).  Dropping the snapshot without this call leaks both the
        page bytes and the ``exported_bytes`` gauge they are counted
        under — the budget ladder would see phantom pressure forever.
        Checkpoint snapshots (``exported=False``) never moved accounting
        out of the controller, so dropping them is free."""
        if snap.stashed and snap.exported:
            self.ctl.release_exported(snap.stashed)
        snap.stashed = None

    def checkpoint_lane(self, lane: int) -> Optional[LaneSnapshot]:
        """Non-destructive ``_suspend_decode``: capture a resume-exact
        snapshot of a decoding lane WITHOUT freeing it — the replica
        router's periodic checkpoint, mirrored off-engine so a crashed
        replica's lanes can be re-placed on a survivor token-identically
        from the last checkpoint.

        The lane keeps running; the controller keeps owning its host
        store (``copy_lane`` shares the immutable page payloads and
        copies the mutable freeze metas), so ``exported_bytes`` does not
        move — the snapshot is marked ``exported=False`` and both
        ``resume_lane`` and ``discard_snapshot`` skip the accounting they
        would move back for a real export.  Returns None for an idle lane
        or one still mid-chunked-prefill (no decode progress to
        checkpoint — failover re-prefills those)."""
        self.flush()
        l = self.lanes[lane]
        pp = self.prefills.get(lane)
        if l.request is None or (pp is not None and not pp.over):
            return None
        snap = self._snap_host(lane)
        pool, fstate = self._pull_lanes([lane])
        snap.pool = {f: a.copy() for f, a in pool.items()}
        snap.fstate = {f: a.copy() for f, a in fstate.items()}
        rec = jax.device_get(self.state.recovery)
        snap.recovery = {f: np.asarray(a)[lane].item()
                         for f, a in zip(RecoveryState._fields, rec)}
        snap.tail_slot = self.tail_slot[:, lane].copy()
        snap.stashed = self.ctl.copy_lane(lane)
        snap.pending_thaw = lane in self.pending_thaws
        snap.urgency = float(self._urgency[lane])
        snap.exported = False
        self.events.append({"event": "checkpoint", "uid": snap.req.uid,
                            "lane": lane, "wall_step": self.wall_step,
                            "generated": len(snap.generated),
                            "stashed_pages": len(snap.stashed)})
        return snap

    def _retire(self, lane: int) -> Request:
        l = self.lanes[lane]
        req = l.request
        req.result = np.asarray(l.generated[: req.n_tokens], np.int32)
        req.telemetry.tokens = req.result[None, :]
        self._finalize_status(req)
        self.events.append({"event": "finish", "uid": req.uid, "lane": lane,
                            "wall_step": self.wall_step})
        l.request = None
        l.generated = []
        l.history = []
        # unmap the lane's pages on device (attention skips them), drop its
        # host store, staged prefetches and any pending thaw so nothing
        # leaks into the lane's next occupant
        self.state = self._reset_lane(state=self.state, lane=jnp.int32(lane))
        self.ctl.drop_lane(lane)
        self.pending_thaws.discard(lane)
        self._urgency[lane] = 0.0
        self._set_lane_sampling(lane, SamplingParams.greedy())
        return req
