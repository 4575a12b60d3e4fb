"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--control 1]

One process: it fails unless JAX's devices are TPUs (as many as the cell
asks for), turns on JAX's persistent compilation cache, makes the weights
on the device from the seed, builds the engine the way ``launch/serve.py``
does, warms the cell's shapes, runs the traffic's set-up, measures for
``--seconds`` and then checks what the window served against the plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from a profiler trace of the window.
``--control 1`` puts the int8 control in the program's place in the same
comparison: ``correct`` and ``checks`` are then the control's, and the
program's own come beside them as ``program_correct`` and
``program_checks`` (for setting limits; the benchmark's own runs never
pass it).

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``, each number compared beside its
limit (also the last lines of stderr).
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None, require_tpu: bool = True, root=None) -> dict:
    import harness as H
    import traffic

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--events-out", default=None,
                    help="with --trace 1: write the trace's first "
                         "--events-ms of the window, with the window's "
                         "counts, to this JSON file (a recorded trace for "
                         "the reduction's tests)")
    ap.add_argument("--events-ms", type=float, default=400.0)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload, root or H.ROOT)

    import jax
    if require_tpu:
        device = H.check_device(cell.chips)
    else:
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(jax.devices())}
    from repro.launch import serve
    from repro.serving.scheduler import Scheduler
    from repro.serving.server import AsyncServingEngine
    H.log(f"device {device}; compile cache {serve.enable_compile_cache()}")
    clock = H.CompileClock()

    cfg = H.model_config(cell.config, cell.root)
    mix = cell.mix
    params = H.make_params(cell.config, cfg, H.weight_seed(args.seed))
    eng = serve.build_engine(cfg, params, H.serving_config(mix, args.seed))
    sched = Scheduler(eng)
    plan = traffic.plan(mix, args.seed, args.seconds, cfg.vocab_size)
    vis = H.Visibility(eng)
    H.warm(eng, sched, mix, plan)
    vis.reset()
    H.log(f"built and warmed in {time.perf_counter() - T_PROCESS:.1f} s, "
          f"compile {clock.seconds:.1f} s over {clock.n} programs")
    inst = H.Instrument(sched) if args.trace else None
    aeng = AsyncServingEngine(sched)
    n_compiles = clock.n
    run = asyncio.run(H.drive(aeng, eng, mix, plan, args.seconds,
                              bool(args.trace), inst))
    setup_s = run.t0 - T_PROCESS
    vis.land()
    in_window = clock.n - n_compiles
    mem = jax.devices()[0].memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    device["memory_peak_bytes"] = peak
    H.log(f"window {run.t1 - run.t0:.2f} s after {setup_s:.1f} s of "
          f"set-up; {in_window} compiles inside it; peak {peak} bytes")
    eng_info = {"n_lanes": eng.n_lanes, "L": eng.L_attn,
                "P_total": eng.P_total, "page": eng.page,
                "device_kind": device["kind"]}
    attempted, failed = H.W.attempted_failed(run.recs, run.t0, run.t1,
                                             H.OK_STATUS)
    if args.trace:
        import tracing
        events = tracing.load(str(H.TRACE_DIR))
        run.trace = tracing.reduce(events)
        if args.events_out:
            tracing.excerpt(events, args.events_ms, args.events_out, {
                "config": cell.config["name"], "engine": eng_info,
                "lane_steps": run.lane_steps, "visible": run.visible,
                "decode_steps": run.after["wall_step"]
                - run.before["wall_step"], "window_s": run.t1 - run.t0})
        shutil.rmtree(H.TRACE_DIR, ignore_errors=True)
        top = sorted(run.trace.module_ns.items(), key=lambda kv: -kv[1])
        H.log("device programs: " + json.dumps(
            [[k, v / 1e9, run.trace.module_calls[k]] for k, v in top[:12]]))
        ops = sorted(run.trace.op_ns.items(), key=lambda kv: -kv[1])
        H.log("device ops: " + json.dumps(
            [[k, v / 1e9, run.trace.op_calls[k]] for k, v in ops[:25]]))
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        metrics = H.per_layer(cell, run, eng_info)
    else:
        metrics = H.end_to_end(cell, run, setup_s, peak)

    sample = H.check_sample(cell, run, args.seed)
    # the program's state goes before the reference runs: a process's
    # peak never falls again, and it was read above
    del aeng, sched, eng, params, inst
    gc.collect()
    H.log(f"after freeing the program: {len(jax.live_arrays())} arrays, "
          f"{sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f} GB live")
    found = H.reference_gaps(cell, sample, vis, args.seed,
                             bool(args.control)) if sample else {"tokens": 0}
    correct, checks = H.judge(cell, found.get("control", found))
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = run.trace.breakdown()
    result["compiles_in_window"] = in_window
    result["reference"] = found
    if args.control:
        result["program_correct"], result["program_checks"] = \
            H.judge(cell, found)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
