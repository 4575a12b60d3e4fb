"""Reduction of a profiler trace to the per-layer metrics' inputs: busy
union, idle share, idle gaps by the harness span open in them, kernel and
program device time, and the kernel's roofline share."""
import types

import pytest

import harness as H
import tracing

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6


def events():
    ev = [(HOST, "python", "bench:window", 0.0, 100 * MS)]
    # two decode steps of 10 ms, each a program holding two kernel calls
    # and one overlapping fusion; an 8 ms install push between them
    for t0 in (10 * MS, 60 * MS):
        ev.append((DEV, "XLA Modules", "jit__unknown(123)", t0, 10 * MS))
        ev += [(DEV, "XLA Ops", "paged_decode_attention", t0, 2 * MS),
               (DEV, "XLA Ops", "fusion.1", t0 + 1 * MS, 4 * MS),
               (DEV, "XLA Ops", "paged_decode_attention", t0 + 6 * MS,
                2 * MS)]
        ev.append((HOST, "worker", "bench:Scheduler.step", t0 - 2 * MS,
                   14 * MS))
        ev.append((HOST, "worker", "bench:step_once", t0 - 1 * MS,
                   12 * MS))
    ev.append((HOST, "worker", "bench:push_lanes", 20 * MS, 20 * MS))
    ev.append((DEV, "XLA Ops", "copy.7", 40 * MS, 5 * MS))
    # another unnamed program (a lane reset) that runs no kernel
    ev.append((DEV, "XLA Modules", "jit__unknown(9)", 46 * MS, 0.01 * MS))
    # outside the window: ignored
    ev.append((DEV, "XLA Ops", "fusion.1", 150 * MS, 50 * MS))
    return ev


def test_busy_union_gaps_and_device_time():
    r = tracing.reduce(events())
    assert r.window_s == pytest.approx(0.1)
    # busy: [10,15] [16,18] [40,45] [60,65] [66,68] ms = 19 ms
    assert r.busy_s == pytest.approx(0.019)
    assert r.ops_matching("paged_decode") == (pytest.approx(0.008), 4)
    assert r.modules_holding("paged_decode_attention") == \
        (pytest.approx(0.020), 2)
    assert r.modules_holding("no such op") == (0.0, 0)
    by = {round((e - s) / MS, 3): lbl for s, e, lbl in r.gaps}
    assert by[10.0] == "outside spans"          # [0, 10)
    assert by[1.0] == "step_once"               # [15, 16), inside the step
    assert by[22.0] == "push_lanes"             # [18, 40)
    assert by[15.0] == "outside spans"          # [45, 60)
    assert by[32.0] == "outside spans"          # [68, 100)
    bd = r.breakdown(3)
    assert {n for n, _ in bd["device_ops"][:2]} == \
        {"fusion.1", "paged_decode_attention"}
    assert bd["device_ops"][2] == ["copy.7", pytest.approx(0.005)]
    assert bd["idle_gaps"][0] == ["outside spans", pytest.approx(0.032)]


def test_metric_readers_on_the_reduced_trace():
    r = tracing.reduce(events())
    cfg = {"architecture": "mistral",
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 64, "hidden_size": 256, "intermediate_size": 512,
           "num_hidden_layers": 2, "vocab_size": 512}
    run = H.Run([], t0=0.0, t1=0.1, lane_steps=4, visible=2000.0,
                steps=[(-1.0, 0.03), (0.05, 0.2)])
    ctx = types.SimpleNamespace(
        run=run, window_s=0.1, config=cfg, trace=r,
        engine={"n_lanes": 2, "P_total": 8, "page": 64},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        work=H.work_modules(), W=H.W)
    idle = H.load_module(H.BENCH / "metrics/device_idle_share.py").read(ctx)
    assert idle == pytest.approx(81.0)
    roof = H.load_module(
        H.BENCH / "metrics/paged_attn_roofline.py").read(ctx)
    w = ctx.work.paged_decode_attn.work(cfg, visible=2000.0, calls=4,
                                        lanes=2, pages=8, page=64)
    assert roof == pytest.approx(100 * w["bytes"] / 819e9 / 0.008)
    assert 0 < roof < 100
    mfu = H.load_module(H.BENCH / "metrics/decode_step_mfu.py").read(ctx)
    f = ctx.work.decode_step.flops(cfg, 4, 2000.0)
    assert mfu == pytest.approx(100 * f / (0.020 * 197e12))
    loop = H.load_module(H.BENCH / "metrics/serve_loop_share.py").read(ctx)
    # steps (-1, 0.03) and (0.05, 0.2) clipped to the window [0, 0.1]
    assert loop == pytest.approx(100 * (1 - (0.03 + 0.05) / 0.1))


def test_a_reader_with_nothing_to_read_returns_nothing():
    ctx = types.SimpleNamespace(run=H.Run([]), trace=None, window_s=1.0,
                                work=H.work_modules())
    for m in ("device_idle_share", "paged_attn_roofline", "decode_step_mfu",
              "serve_loop_share"):
        assert H.load_module(H.BENCH / f"metrics/{m}.py").read(ctx) is None


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench:window"):
        tracing.reduce([(DEV, "XLA Ops", "fusion", 0.0, 1.0)])


def test_a_recorded_chip_trace():
    """The first 200 ms of a traced run's window on the chip (``bench/run.py
    --trace 1 --events-out``, nemo-longctx-decode): device busy and idle,
    the kernel's device time found by name, the decode step found by the
    kernel it runs, and the kernel's roofline share under 100%."""
    import json
    import pathlib
    rec = json.loads((pathlib.Path(__file__).parent
                      / "data/trace_excerpt.json").read_text())
    meta = rec["meta"]
    r = tracing.reduce([tuple(e) for e in rec["events"]])
    assert r.n_devices == 1 and r.window_s == pytest.approx(0.2)
    assert 0 < r.busy_s < r.window_s
    work = H.work_modules().paged_decode_attn
    k_s, calls = r.ops_matching(work.OP)
    step_s, steps = r.modules_holding(work.OP)
    L = meta["engine"]["L"]
    # a step cut by the excerpt's end keeps only the kernel calls it began
    assert steps >= 1 and (steps - 1) * L < calls <= steps * L
    assert 0 < k_s < step_s <= r.busy_s
    cfg = json.loads((H.BENCH / "configs" / f"{meta['config']}.json")
                     .read_text())
    visible = meta["visible"] / meta["decode_steps"] * steps
    w = work.work(cfg, visible=visible, calls=calls,
                  lanes=meta["engine"]["n_lanes"],
                  pages=meta["engine"]["P_total"], page=meta["engine"]["page"])
    t, bound = work.least_seconds(w, H.peaks("TPU v5 lite"))
    assert bound == "memory" and 0 < t / k_s < 1
