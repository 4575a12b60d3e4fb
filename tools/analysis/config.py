"""The repo's analysis configuration (plain Python — the container's
Python 3.10 has no stdlib TOML parser, and a config that can use
``frozenset`` directly needs no schema layer).

Three knobs matter; see docs/analysis.md for the full story:

* ``hot_functions`` — the serving hot path: everything executed per
  decode step or per boundary tick.  Admission-time helpers that run
  once per request (``_restore_host``, bucket padding) and the static
  ``Engine.generate`` reference loop (per-step sync *by design* — it is
  the paper-protocol baseline the continuous engines are measured
  against) are deliberately not listed.
* ``device_roots`` — identifiers that mark an expression as
  device-resident.  The engines keep host mirrors in distinctly-named
  attributes (``self.pos``, ``self.tok``, ring-drained ``host``/``toks``
  dicts), so the root set cleanly splits the two worlds.
* ``bucketed_functions`` — functions whose inline shape-constructor
  calls iterate a *closed* bucket table (compile-once warm-up loops).
"""
from .framework import Config

REPO_CONFIG = Config(
    hot_functions=frozenset({
        # dense continuous engine: per-step loop + in-serve admission
        "ContinuousEngine.admit",
        "ContinuousEngine.resume_lane",
        "ContinuousEngine.step_once",
        "ContinuousEngine._commit_step",
        # paged engine: step loop, boundary tick, DMA pulls/pushes,
        # chunked prefill, speculative thaw staging, remap installs
        "PagedContinuousEngine.step_once",
        "PagedContinuousEngine._commit_step",
        "PagedContinuousEngine._boundary_tick",
        "PagedContinuousEngine._controller_pass",
        "PagedContinuousEngine._pull_lanes",
        "PagedContinuousEngine._push_lanes",
        "PagedContinuousEngine._prefill_tick",
        "PagedContinuousEngine._install",
        "PagedContinuousEngine._maybe_prefetch",
        "PagedContinuousEngine._prefetch_lane",
        "PagedContinuousEngine._run_remaps",
        # shared lane machinery (ring drain runs every step)
        "_LaneEngineBase._drain_ring",
        "_LaneEngineBase._push_admit_token",
        "_LaneEngineBase._lane_params",
        # chaos hardening: breaker-gated ring depth + NaN quarantine run
        # every step; Endpoint.call wraps every guarded transfer
        "_LaneEngineBase._ring_guard",
        "_LaneEngineBase._quarantine_scan",
        "_LaneEngineBase._poison_lane",
        "Endpoint.call",
        # host-side paging controller: ticked at every page boundary
        "PagedController.tick",
        "PagedController.thaw_lane",
        "PagedController._kv_transfer",
        "PagedController._install_page",
        "PagedController._evict_coldest",
        "PagedController.ensure_resident",
        # budget-guarded host-stash writer (every stash allocation)
        "PagedController._store_put",
        # per-page quantization: freeze-time in-place pass + swap-out
        # narrowing + thaw installs all run inside the boundary tick
        "PagedController._quantize_frozen_resident",
        "PagedController._store_payload",
        "PagedController._install_kv",
        # core.quant numeric recipe (module-level, hence bare names):
        # called per quantized page on freeze/stash/thaw/rewind
        "quantize_page",
        "dequantize_page",
        "page_scales",
        "narrow_payload",
        # page-batched offload round-trip (dense engine's commit path)
        "HostOffloadController.sync",
        # replica router: the tick loop, the per-tick heartbeat compare
        # and the failover re-place path are all host-side bookkeeping
        # and must stay free of device syncs (checkpoint_lane is NOT
        # listed — it is a deliberate blocking pull, like suspend_lane)
        "ReplicaRouter.step",
        "ReplicaRouter._heartbeat",
        "ReplicaRouter._failover",
        # multi-tenant server front end: the WFQ admission scan runs at
        # every free-lane fill, tenancy accounting runs per lane per
        # step, and the async engine's op/pump pair runs between every
        # scheduler step on the event loop — all pure host bookkeeping
        "Scheduler._pop_admissible",
        "TenancyController.may_admit",
        "TenancyController.note_progress",
        "AsyncServingEngine._apply_ops",
        "AsyncServingEngine._pump_all",
    }),
    device_roots=frozenset({
        "state",        # self.state / lane_state / decode state pytrees
        "lane_state",
        "scratch",      # pp.scratch prefill cache
        "logits",
        "dev",          # _pull_lanes' gathered per-lane device tuples
        "cache",        # KVCache pytrees handed to the offloader
        "info",         # decode_step telemetry pytree (pre-ring)
    }),
    bucketed_functions=frozenset({
        # warm-up loops over the closed chunk/bucket tables: each member
        # shape compiles exactly once before serving starts
        "PagedContinuousEngine.warm_prefill",
    }),
)
