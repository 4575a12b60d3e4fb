"""The program's ``repro:`` spans reach the per-layer metrics' readers
through the one trace reduction, with their thread and stats, and the
five span metrics read what ``repro.analysis.spans`` reads."""
import json
import pathlib
import types

import pytest

import harness as H
import tracing
from repro.analysis import spans as S

ROOT = pathlib.Path(__file__).resolve().parents[2]
EXCERPT = ROOT / "tests/data/spans_excerpt.json"
# each reader and the key of ``repro.analysis.spans.summary`` it matches
READERS = ["kv_tick_ms", "lane_pull_gbps", "lane_push_gbps",
           "plain_step_ms", "tick_ms"]


def excerpt_events():
    """``spans_excerpt.json`` (6.6 s of a traced chip run around its first
    tick, in ``spans.save``'s format) as the events ``tracing.load``
    gives: the program's spans with thread and stats, one window span, and
    device ops over the busy intervals."""
    rec = json.loads(EXCERPT.read_text())
    lo, hi = rec["window"]
    ev = [("/host:CPU", "python3", tracing.WINDOW, lo, hi - lo)]
    for name, s, d, thread, stats in rec["spans"]:
        ev.append(("/host:CPU", thread.split("/")[0], "repro:" + name, s, d,
                   thread, stats))
    ev += [("/device:TPU:0", "XLA Ops", "fusion", s, e - s)
           for s, e in rec["busy"]]
    return ev


def read(name, trace):
    ctx = types.SimpleNamespace(trace=trace)
    return H.load_module(H.BENCH / f"metrics/{name}.py").read(ctx)


@pytest.mark.parametrize("name", READERS)
def test_span_readers_equal_the_programs_own_summary(name):
    want = S.summary(*S.load(str(EXCERPT)))[name]
    r = tracing.reduce(excerpt_events())
    got = read(name, r)
    assert want is not None and got == pytest.approx(want, rel=1e-12)


def test_the_excerpts_spans_keep_their_thread_and_stats():
    r = tracing.reduce(excerpt_events())
    assert len(r.spans) == 51 and r.spans == sorted(
        r.spans, key=lambda s: s.start)
    push = [s for s in r.spans if s.name == "engine.push_lanes"]
    assert push == [tracing.Span(
        "engine.push_lanes", 4120956210.0, 2471532485.0, "python3/29",
        {"lanes": 4, "kv": 1, "bytes": 2140310880})]
    assert r.span_ms("engine.tick") == pytest.approx(6092.874558)
    assert r.span_gbps("engine.push_lanes", kv=0) is None


def test_span_readers_with_nothing_to_read_return_nothing():
    bare = tracing.reduce([("/host:CPU", "python", tracing.WINDOW, 0.0, 1e9),
                           ("/device:TPU:0", "XLA Ops", "fusion", 0.0, 5e8)])
    assert bare.spans == []
    for name in READERS:
        assert read(name, bare) is None
        assert read(name, None) is None


def test_a_span_that_starts_outside_the_window_is_not_read():
    ev = [("/host:CPU", "python", tracing.WINDOW, 10.0, 100.0),
          ("/host:CPU", "w", "repro:engine.tick", 5.0, 20.0, "w/0", {}),
          ("/host:CPU", "w", "repro:engine.tick", 50.0, 4e6, "w/0", {})]
    assert tracing.reduce(ev).span_ms("engine.tick") == pytest.approx(4.0)


def test_load_keeps_program_spans_with_thread_and_stats(tmp_path):
    """A CPU capture: a ``repro:`` span comes back with its line's thread
    and its keyword stats, a ``bench:`` span as a plain event."""
    import jax
    import jax.numpy as jnp
    tracing.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.TraceAnnotation("repro:engine.pull_lanes",
                                          bytes=4096, lanes=2):
            jnp.ones(8).block_until_ready()
        with jax.profiler.TraceAnnotation("bench:step_once"):
            pass
    tracing.stop()
    ev = tracing.load(str(tmp_path))
    pull = [e for e in ev if e[2] == "repro:engine.pull_lanes"]
    assert len(pull) == 1 and len(pull[0]) == 7
    plane, line, _, _, _, thread, stats = pull[0]
    assert thread.startswith(line + "/") and stats == {"bytes": 4096,
                                                       "lanes": 2}
    assert [len(e) for e in ev if e[2] == "bench:step_once"] == [5]
    r = tracing.reduce(ev)
    assert r.spans[0].name == "engine.pull_lanes"
    assert r.span_gbps("engine.pull_lanes") == pytest.approx(
        4096 / r.spans[0].dur)
