"""Serving launcher: drive any --arch config through one of the three
serving paths (see docs/serving.md for the architecture):

* default — ``ContinuousEngine``: continuous batching with per-lane
  admission/retirement over a dense (n_lanes, max_seq) KV cache.
* ``--paged`` — ``PagedContinuousEngine``: bounded-HBM decode over a
  per-lane active page pool (``--pages``) with chunked prefill
  (``--prefill-chunk``) and host page swapping; with ``--recovery`` the
  entropy ladder also thaws stashed pages and performs page-granular
  Rewalk rewinds (docs/recovery.md).
* ``--static`` — the pre-continuous-batching fixed-batch FIFO baseline
  (head-of-line blocking: every lane runs for the batch max n_tokens).

Continuous paths serve through the SLO-aware scheduler: ``--priority``
assigns a strict class to the submitted requests, ``--deadline-ms`` /
``--slo-tps`` attach per-request completion deadlines (EDF within a
class), and ``--background N`` floods N low-priority long generations
first so deadlined requests exercise freeze-native lane preemption
(``--no-preempt`` to disable; see docs/serving.md).

Every engine runs on JAX's default device: ``--tiny`` on the CPU, the
full-width configs on one accelerator.  ``model_config``, ``init_model`` and
``build_engine`` are the construction path, shared with ``chip_smoke.py``;
``enable_compile_cache`` keeps compiled programs across runs.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --tiny \
        --requests 8 --tokens 128
    PYTHONPATH=src python -m repro.launch.serve --tiny --paged --recovery
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import Optional

import jax
import numpy as np

from repro.configs import get_config, list_archs
from repro.configs.base import ModelConfig
from repro.models import model as MD
from repro.serving.config import ServingConfig
from repro.serving.engine import (ContinuousEngine, Engine,
                                  PagedContinuousEngine)
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Scheduler, StaticScheduler


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the directory (JAX reads
    it itself) and no other is set.  Otherwise the cache lives at the
    fixed ``<checkout>/.jax_cache``: the path is part of the cache key, so
    it must not move between runs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def model_config(arch: str, *, tiny: bool = False,
                 num_layers: Optional[int] = None,
                 quantile_tau: float = 0.45,
                 recovery: bool = True) -> ModelConfig:
    """The served model: ``arch`` (or its ``-tiny`` variant), optionally
    cut to its first ``num_layers`` layers, with this launcher's freeze
    schedule (adaptive-tau quantile ``quantile_tau``; 0 = the paper's
    fixed tau) and entropy-guided recovery on or off."""
    cfg = get_config(arch + ("-tiny" if tiny else ""))
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_layers=num_layers)
    if quantile_tau > 0:
        cfg = dataclasses.replace(cfg, freeze=dataclasses.replace(
            cfg.freeze, tau_mode="quantile", quantile=quantile_tau,
            window=16, k_soft=1.0, entropy_abs_threshold=1e9))
    return dataclasses.replace(cfg, freeze=dataclasses.replace(
        cfg.freeze, recovery_enabled=recovery))


def init_model(cfg: ModelConfig, seed: int = 0):
    """Random weights from ``seed``, made by one jitted program on the
    default device, so no float32 copy of a full-width model is held."""
    return jax.jit(MD.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def build_engine(cfg: ModelConfig, params, sv: ServingConfig):
    """The continuous engine ``sv`` describes: ``PagedContinuousEngine``
    when it sets ``max_active_pages``, else ``ContinuousEngine``."""
    if sv.max_active_pages is not None:
        return PagedContinuousEngine(cfg, params, serving=sv)
    return ContinuousEngine(cfg, params, serving=sv)


def _serve_http(args, mk_engine) -> None:
    """--http: stand up the multi-tenant SSE streaming front end over one
    continuous engine (see serving/server.py) and serve until killed.

        curl -N localhost:PORT/v1/generate -H 'X-Tenant: gold' \\
             -d '{"prompt": [1, 2, 3], "n_tokens": 32}'
    """
    import asyncio

    from repro.serving.server import AsyncServingEngine, ServingServer
    from repro.serving.tenancy import TenancyController, TenantConfig
    if args.static or args.replicas > 1:
        raise SystemExit("--http serves one continuous engine "
                         "(no --static / --replicas)")
    tenancy = None
    if args.tenants:
        cfgs = []
        for spec in args.tenants.split(","):
            f = spec.split(":")
            cfgs.append(TenantConfig(
                f[0], weight=float(f[1]) if len(f) > 1 else 1.0,
                max_lanes=int(f[2]) if len(f) > 2 else None,
                tokens_per_s=float(f[3]) if len(f) > 3 else None))
        tenancy = TenancyController(cfgs)
    sched = Scheduler(mk_engine(), preemption=args.preempt,
                      tenancy=tenancy)

    async def _run():
        srv = ServingServer(AsyncServingEngine(sched), port=args.http)
        await srv.start()
        print(f"serving on http://{srv.host}:{srv.port}  "
              f"(POST /v1/generate streams SSE; GET /v1/health, "
              f"/v1/stats)", flush=True)
        try:
            await asyncio.Event().wait()
        finally:
            await srv.close()

    try:
        asyncio.run(_run())
    except KeyboardInterrupt:
        pass


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b", choices=list_archs())
    ap.add_argument("--tiny", action="store_true",
                    help="reduced config (CPU scale)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="number of engine lanes")
    ap.add_argument("--tokens", type=int, default=128)
    ap.add_argument("--max-seq", type=int, default=512)
    ap.add_argument("--no-freeze", action="store_true")
    ap.add_argument("--static", action="store_true",
                    help="static FIFO batching baseline instead of "
                         "continuous batching")
    ap.add_argument("--paged", action="store_true",
                    help="bounded-HBM paged engine (chunked prefill, "
                         "O(pages) device KV per lane)")
    ap.add_argument("--pages", type=int, default=8,
                    help="device-resident pages per lane (--paged)")
    ap.add_argument("--prefill-chunk", type=int, default=64)
    ap.add_argument("--recovery", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="entropy-guided recovery: the escalation ladder "
                         "(SR/WR/FR/RR) un-freezes KV on entropy spikes; "
                         "on --paged this includes host thaws of stashed "
                         "pages and page-granular rewinds "
                         "(--no-recovery = freeze-timer expiry only)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through ReplicaRouter over N in-process "
                         "engine replicas: SLO-aware placement, heartbeat "
                         "health-checking, incremental lane checkpoints "
                         "and zero-loss failover via freeze-native lane "
                         "migration (docs/robustness.md)")
    ap.add_argument("--kill-replica-at", type=int, default=None,
                    metavar="TICK",
                    help="crash replica 0 at this router tick (the "
                         "deterministic replica_crash fault site) to demo "
                         "failover; requires --replicas > 1")
    ap.add_argument("--checkpoint-every", type=int, default=8,
                    help="router ticks between incremental lane "
                         "checkpoints (--replicas > 1; smaller = less "
                         "repeated decode after a crash, more checkpoint "
                         "DMA)")
    ap.add_argument("--priority", type=int, default=0,
                    help="strict priority class for the submitted requests "
                         "(0 = most important; higher classes can be "
                         "preempted for lower ones)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request completion deadline (ms after "
                         "submission); deadlines order requests EDF within "
                         "a class and arm preemption")
    ap.add_argument("--slo-tps", type=float, default=None,
                    help="decode-rate SLO (tokens/s) converted to a "
                         "completion deadline per request")
    ap.add_argument("--background", type=int, default=0,
                    help="submit N extra priority-9 long generations first "
                         "(contention for the preemption demo)")
    ap.add_argument("--preempt", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="freeze-native lane preemption: suspend a running "
                         "lower-priority lane (stashing its pages to the "
                         "host store on --paged) when a deadline would "
                         "otherwise be missed (--no-preempt = admission "
                         "reordering only)")
    ap.add_argument("--stash-budget-mb", type=float, default=None,
                    help="host-stash memory budget (MiB); engages the "
                         "graceful-degradation ladder as stash pressure "
                         "rises (deny prefetch -> deepen freeze timers -> "
                         "throttle admissions -> shed lanes; "
                         "docs/robustness.md)")
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="enable deterministic fault injection on the "
                         "DMA/stash paths with this seed (retries, "
                         "breaker fallbacks and quarantine exercise the "
                         "chaos hardening; docs/robustness.md)")
    ap.add_argument("--chaos-rate", type=float, default=0.05,
                    help="per-site fault rate for --chaos-seed")
    ap.add_argument("--kv-quant", default="none",
                    choices=("none", "int8", "fp8"),
                    help="lossy per-page quantization of frozen/stashed KV "
                         "pages (core/quant.py): on --paged the device "
                         "pool's frozen pages and the host stash store a "
                         "1-byte payload with per-page per-kv-head scales "
                         "(dequantized in-kernel at attention time); on "
                         "the dense path the host stash alone is "
                         "quantized.  'fp8' needs ml_dtypes "
                         "float8_e4m3fn.  'none' is bit-identical to the "
                         "unquantized engine (docs/quantization.md)")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve over HTTP instead of driving a batch "
                         "trace: multi-tenant SSE streaming front end "
                         "(POST /v1/generate, GET /v1/health, /v1/stats; "
                         "PORT 0 = ephemeral; docs/serving.md)")
    ap.add_argument("--tenants", default=None,
                    metavar="NAME:WEIGHT[:LANES[:TPS]],...",
                    help="register tenants for --http, e.g. "
                         "'gold:3,free:1:1:50' — weighted fair sharing "
                         "plus optional concurrent-lane and tokens/s caps")
    ap.add_argument("--temperature", type=float, default=0.7)
    ap.add_argument("--quantile-tau", type=float, default=0.45,
                    help="adaptive-tau quantile (0 = paper fixed tau)")
    ap.add_argument("--async", dest="async_pipeline",
                    action=argparse.BooleanOptionalAction, default=True,
                    help="async DMA pipeline: the per-step token/telemetry "
                         "fetch rides a double-buffered ring (consumed one "
                         "step later), boundary-tick pool swaps batch into "
                         "one transfer pair, and on --paged likely thaws "
                         "are prefetched into device staging slots "
                         "(--no-async = block on every step's fetch — the "
                         "pre-pipeline baseline; identical decisions, and "
                         "bit-identical tokens under a deterministic "
                         "prefill-chunk schedule, see docs/serving.md)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = model_config(args.arch, tiny=args.tiny,
                       quantile_tau=args.quantile_tau,
                       recovery=args.recovery)
    params = init_model(cfg)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    mode = "static" if args.static else \
        ("paged-continuous" if args.paged else "continuous")
    dev = jax.devices()[0]
    print(f"arch={cfg.name} params={n/1e6:.1f}M "
          f"freeze={not args.no_freeze} batching={mode} "
          f"device={dev.platform}:{dev.device_kind}")

    chaos = None
    if args.chaos_seed is not None:
        from repro.serving.faults import ChaosConfig
        chaos = ChaosConfig(seed=args.chaos_seed,
                            rates={s: args.chaos_rate for s in
                                   ("pull", "push", "ring", "stage")})
    budget = int(args.stash_budget_mb * 2**20) \
        if args.stash_budget_mb is not None else None
    sv = ServingConfig(max_seq=args.max_seq, n_lanes=args.batch,
                       enable_freeze=not args.no_freeze,
                       async_pipeline=args.async_pipeline,
                       prefill_chunk=args.prefill_chunk,
                       max_active_pages=args.pages if args.paged else None,
                       chaos=chaos, stash_budget_bytes=budget,
                       kv_quant=args.kv_quant)

    def mk_engine():
        return build_engine(cfg, params, sv)

    if args.http is not None:
        _serve_http(args, mk_engine)
        return

    router = None
    if args.static:
        eng = Engine(cfg, params, max_seq=args.max_seq,
                     enable_freeze=not args.no_freeze)
        sched = StaticScheduler(eng, batch_size=args.batch)
    elif args.replicas > 1:
        from repro.serving.router import ReplicaRouter
        kill = None if args.kill_replica_at is None \
            else (0, args.kill_replica_at)
        router = ReplicaRouter([mk_engine() for _ in range(args.replicas)],
                               checkpoint_every=args.checkpoint_every,
                               kill_at=kill,
                               sched_kw=dict(preemption=args.preempt))
        eng = None
        sched = router   # submit()/run()/done/metrics-compatible front end
    else:
        eng = mk_engine()
        sched = Scheduler(eng, preemption=args.preempt)
    rng = np.random.RandomState(0)
    if not args.static:
        for _ in range(args.background):
            sched.submit(rng.randint(0, cfg.vocab_size, size=32),
                         max(args.tokens * 2, 64), SamplingParams.greedy(),
                         priority=9)
    for _ in range(args.requests):
        sp = SamplingParams(temperature=args.temperature)
        if args.static:
            sched.submit(
                rng.randint(0, cfg.vocab_size, size=rng.randint(16, 64)),
                args.tokens, sp)
        else:
            sched.submit(
                rng.randint(0, cfg.vocab_size, size=rng.randint(16, 64)),
                args.tokens, sp, priority=args.priority,
                deadline_ms=args.deadline_ms,
                slo_tokens_per_s=args.slo_tps)
    t0 = time.time()
    sched.run()
    dt = time.time() - t0
    total = sum(len(r.result) for r in sched.done.values())
    print(f"served {len(sched.done)} requests / {total} tokens in {dt:.1f}s "
          f"({1e3*dt/max(total,1):.1f} ms/token)")
    if router is not None:
        rep = router.report()
        steps = sum(h["health"]["wall_step"] for h in rep["replicas"])
        print(f"router: {rep['n_replicas']} replicas ({rep['n_live']} "
              f"live)  {rep['ticks']} ticks / {steps} engine steps  "
              f"failovers={rep['n_failovers']} "
              f"(ckpt-recovered={rep['recovered_with_checkpoint']} "
              f"reprefill={rep['recovered_reprefill']} "
              f"requeued={rep['requeued_items']})  "
              f"rebalanced={rep['n_rebalanced']}  "
              f"lost={rep['lost_requests']}")
    if not args.static and router is None:
        # first token of each request comes from its prefill, not a decode
        # step, so decode-step utilization excludes it
        decode_tokens = total - len(sched.done)
        util = 100 * decode_tokens / max(eng.wall_step * args.batch, 1)
        print(f"jitted steps: {eng.wall_step}  lane utilization: {util:.0f}%")
        if args.paged:
            print(f"device KV pool: {eng.kv_device_bytes} bytes "
                  f"(peak {eng.peak_kv_bytes} incl. prefill scratch)  "
                  f"page swaps: {eng.ctl.n_swap_out} out / "
                  f"{eng.ctl.n_swap_in} in / {eng.ctl.n_thaw} thawed")
            if eng.ctl.n_thaw:
                print(f"thaw installs: {eng.ctl.n_thaw_remap} remap-only "
                      f"(staged) / {eng.ctl.n_thaw_upload} uploaded")
            if args.kv_quant != "none":
                print(f"kv-quant({args.kv_quant}): "
                      f"{eng.ctl.n_quantized_pages} pages quantized  "
                      f"packed device savings now "
                      f"{eng.ctl.device_savings_bytes} bytes")
        s = eng.stats
        print(f"dma: host-blocked {100 * s.host_blocked_fraction:.0f}% of "
              f"steps ({s.blocked_steps}/{s.steps}; "
              f"{'async' if args.async_pipeline else 'sync'} pipeline)  "
              f"blocking {s.blocking_d2h} D2H / {s.blocking_h2d} H2D  "
              f"async {s.async_d2h} D2H / {s.async_h2d} H2D")
        if chaos is not None or budget is not None:
            rs = eng.robust_snapshot()
            print(f"chaos: injected={rs['injected']} "
                  f"retries={rs['retries']} "
                  f"breaker_trips={rs['breaker_trips']}  "
                  f"ladder: deny={rs['ladder_deny']} "
                  f"deepen={rs['ladder_deepen']} "
                  f"throttle={rs['ladder_throttle']} "
                  f"shed={rs['ladder_shed']}  "
                  f"stash peak {rs['peak_stash_bytes']}B"
                  + (f" / budget {rs['stash_budget_bytes']}B"
                     if budget is not None else ""))
    if not args.static:
        if args.recovery:
            rewinds = sum(r.telemetry.rewinds for r in sched.done.values()
                          if r.telemetry is not None)
            print(f"recovery: {rewinds} rewalk rewinds")
        # per-request terminal status: every request ends completed,
        # shed-resumed (survived a ladder shed) or quarantined
        statuses = {}
        for r in sched.done.values():
            statuses[r.status] = statuses.get(r.status, 0) + 1
        print("terminal: " + "  ".join(
            f"{k}={v}" for k, v in sorted(statuses.items())))
        n_pre = sum(r.sched.n_preemptions for r in router.replicas) \
            if router is not None else sched.n_preemptions
        hits = [m["deadline_hit"] for m in sched.metrics.values()
                if m["deadline_hit"] is not None]
        if hits or n_pre:
            rate = 100 * sum(hits) / len(hits) if hits else 100.0
            print(f"slo: {n_pre} preemptions  "
                  f"deadline hit rate {rate:.0f}% "
                  f"({sum(hits)}/{len(hits)} deadlined requests)")


if __name__ == "__main__":
    main()
