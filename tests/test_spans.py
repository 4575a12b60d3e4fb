"""The serving path's ``repro:`` spans: the span tree of a traced boundary
step of the tiny paged engine, the transfer byte counts the spans and
``TransferStats`` carry, the paged engine's program names, and the
reader of ``repro.analysis.spans`` on built and recorded captures."""
import dataclasses
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.analysis import spans as S
from repro.configs import get_config
from repro.models import model as MD
from repro.serving.config import ServingConfig
from repro.serving.engine import PagedContinuousEngine
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Scheduler

DATA = pathlib.Path(__file__).parent / "data"

# every span the program opens (docs/serving.md)
PROGRAM_SPANS = {
    "serve.ops", "sched.step", "sched.schedule", "engine.step",
    "engine.drain", "ring.wait", "engine.commit", "engine.tick",
    "engine.pull_lanes", "engine.unpack", "kv.tick", "engine.push_lanes",
    "engine.remap", "engine.decode", "engine.prefetch", "engine.prefill",
    "engine.install"}


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("llama3-8b-tiny")
    fc = dataclasses.replace(cfg.freeze, page_size=8, window=8,
                             tau_mode="quantile", quantile=0.6, k_soft=0.7,
                             recovery_enabled=False)
    cfg = dataclasses.replace(cfg, freeze=fc, dtype="float32")
    return cfg, MD.init_params(jax.random.PRNGKey(0), cfg)


def _engine(tiny, kv_quant="none", n_lanes=2):
    cfg, params = tiny
    return PagedContinuousEngine(cfg, params, serving=ServingConfig(
        max_seq=256, n_lanes=n_lanes, max_active_pages=4, prefill_chunk=16,
        kv_quant=kv_quant))


def _admit(eng, prompt_len, n_tokens, seed=0):
    """Submit one greedy request and step until its prompt is installed;
    returns the scheduler and the request's lane."""
    sched = Scheduler(eng)
    rng = np.random.RandomState(seed)
    sched.submit(rng.randint(0, eng.cfg.vocab_size, size=prompt_len),
                 n_tokens, SamplingParams.greedy())
    sched.step()
    while eng.prefills:
        sched.step()
    lane = next(i for i, l in enumerate(eng.lanes) if l.request is not None)
    return sched, lane


def _traced(tmp_path, fn):
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return S.load(str(tmp_path))


def _inside(child, parent):
    return (child.thread == parent.thread and parent.start <= child.start
            and child.start + child.dur <= parent.start + parent.dur)


def test_a_boundary_step_opens_the_span_tree(tiny, tmp_path):
    eng = _engine(tiny)
    sched, lane = _admit(eng, 16, 12)        # 16 = two pages: a boundary
    assert eng.pos[lane] % eng.page == 0
    spans, busy, window = _traced(tmp_path, sched.step)
    names = [s.name for s in spans]
    assert set(names) <= PROGRAM_SPANS
    assert len(names) <= 15
    one = {n: next(s for s in spans if s.name == n) for n in set(names)}
    step, tick = one["engine.step"], one["engine.tick"]
    assert names.count("engine.step") == names.count("engine.tick") == 1
    assert step.stats["boundary"] == 1 and step.stats["lanes"] == 1
    assert tick.stats["lanes"] == 1
    assert _inside(step, one["sched.step"])
    assert _inside(tick, step)
    for n in ("engine.pull_lanes", "engine.unpack", "kv.tick",
              "engine.push_lanes", "engine.remap"):
        assert _inside(one[n], tick), n
    assert one["engine.pull_lanes"].start < one["kv.tick"].start \
        < one["engine.push_lanes"].start
    for n in ("engine.drain", "engine.decode", "engine.prefetch"):
        assert _inside(one[n], step) and not _inside(one[n], tick), n
    assert _inside(one["ring.wait"], one["engine.drain"])
    assert len({s.thread for s in spans}) == 1
    # no device plane on the CPU: the whole window is idle, and it is all
    # under the spans or outside them
    idle = S.idle_by_span(spans, busy, window)
    assert sum(idle.values()) == pytest.approx(
        (window[1] - window[0]) / 1e9)
    assert idle["engine.unpack"] > 0


def test_a_plain_step_opens_few_spans(tiny, tmp_path):
    eng = _engine(tiny)
    sched, lane = _admit(eng, 16, 12)
    sched.step()                             # the boundary step
    spans, _, _ = _traced(tmp_path, sched.step)
    names = [s.name for s in spans]
    assert "engine.tick" not in names and len(names) <= 8
    step = next(s for s in spans if s.name == "engine.step")
    assert step.stats["boundary"] == 0


def test_an_admission_is_tied_by_its_uid(tiny, tmp_path):
    eng = _engine(tiny)
    sched = Scheduler(eng)
    uid = sched.submit(np.arange(24) % eng.cfg.vocab_size, 4,
                       SamplingParams.greedy())

    def admit():
        while eng.prefills or not any(l.request for l in eng.lanes):
            sched.step()
    spans, _, _ = _traced(tmp_path, admit)
    pre = [s for s in spans if s.name == "engine.prefill"]
    inst = [s for s in spans if s.name == "engine.install"]
    assert pre and len(inst) == 1
    assert {s.stats["uid"] for s in pre + inst} == {uid}
    assert sum(s.stats["tokens"] for s in pre) == 32    # the 2**k bucket
    assert inst[0].stats["pages"] == 4
    push = [s for s in spans if s.name == "engine.push_lanes"]
    assert len(push) == 1 and _inside(push[0], inst[0])


@pytest.mark.parametrize("kv_quant,n_asked",
                         [("none", 1), ("int8", 1), ("none", 2)],
                         ids=["none", "int8", "none-2of4"])
def test_b_transfer_bytes_are_the_bytes_that_crossed(tiny, tmp_path,
                                                     kv_quant, n_asked):
    """The pull and push spans' ``bytes`` and the change in
    ``TransferStats`` are the nbytes of exactly the asked lanes' columns
    (here 1 or 2 of 4 lanes), one gather and one scatter per lane; an int8
    engine with quantized pages in the pool takes no credit for a packing
    the transfer does not have."""
    eng = _engine(tiny, kv_quant, n_lanes=4)
    sched = Scheduler(eng)
    rng = np.random.RandomState(0)
    for _ in range(n_asked):
        sched.submit(rng.randint(0, eng.cfg.vocab_size, size=64), 40,
                     SamplingParams.greedy())
    sched.step()
    while eng.prefills:
        sched.step()
    for _ in range(30):
        sched.step()
    lanes = [i for i, l in enumerate(eng.lanes) if l.request is not None]
    assert len(lanes) == n_asked
    if kv_quant == "int8":
        assert (np.asarray(eng.state.page_quant) != 0).any(), \
            "no quantized page in the pool: the check would be vacuous"
    eng.flush()
    crossed = {"pull": [], "push": []}
    gather, scatter = eng._gather_lane, eng._scatter_lane

    def spy_gather(arrs, lane):
        out = gather(arrs, lane)
        crossed["pull"].append((int(lane), sum(a.nbytes for a in out)))
        return out

    def spy_scatter(arrs, lane, vals):
        crossed["push"].append((int(lane), sum(v.nbytes for v in vals)))
        return scatter(arrs, lane, vals)
    eng._gather_lane, eng._scatter_lane = spy_gather, spy_scatter
    st = eng.stats
    before = (st.d2h_bytes, st.h2d_bytes)

    def move():
        pool, fstate = eng._pull_lanes(lanes)
        eng._push_lanes(pool, fstate, lanes, kv=True)
        eng._push_lanes(pool, fstate, lanes, kv=False)
    spans, _, _ = _traced(tmp_path, move)
    pull = [s for s in spans if s.name == "engine.pull_lanes"]
    push = [s for s in spans if s.name == "engine.push_lanes"]
    m = n_asked
    # one program call per asked lane, on that lane
    assert [c[0] for c in crossed["pull"]] == lanes
    assert [c[0] for c in crossed["push"]] == lanes + lanes
    pulled = sum(c[1] for c in crossed["pull"])
    pushed = [sum(c[1] for c in crossed["push"][:m]),
              sum(c[1] for c in crossed["push"][m:])]
    assert [s.stats["bytes"] for s in pull] == [pulled]
    assert [s.stats["bytes"] for s in push] == pushed
    assert [s.stats["kv"] for s in push] == [1, 0]
    assert [s.stats["lanes"] for s in pull + push] == [m, m, m]

    def columns(arrs):
        # the asked lanes' columns of the device state, no more
        return sum(a.nbytes // eng.n_lanes * m for a in arrs)
    assert pulled == columns(eng._state_arrs())
    assert pushed[0] == pulled
    assert pushed[1] == columns(eng._state_arrs(eng._META_FIELDS))
    assert st.d2h_bytes - before[0] == pulled
    assert st.h2d_bytes - before[1] == sum(pushed)


def test_the_paged_engine_programs_have_names(tiny, tmp_path):
    """Each jitted program of the paged engine runs under its own name in
    a trace (``jit_<name>`` on the device's program line)."""
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    eng = _engine(tiny)
    want = {"_step": "decode_step_paged", "_sample": "sample_batched_perlane",
            "_chunk": "prefill_chunk", "_gather_lane": "gather_lane",
            "_scatter_lane": "scatter_lane", "_remap_copy": "remap_copy",
            "_stage_write": "stage_write", "_reset_lane": "reset_paged_lane",
            "_set_recovery": "set_recovery", "_rewind": "rewind_paged_lane"}

    def run():
        sched, lane = _admit(eng, 16, 4)
        sched.step()
        L, (page, kvh, hd) = eng.L_attn, eng.state.k.shape[3:]
        z = np.zeros((L, page, kvh, hd), np.float32)
        eng.state = eng._stage_write(
            eng.state, jnp.int32(0), jnp.full(L, -1, jnp.int32), z, z,
            jnp.zeros(L, bool))
        idx = jnp.zeros(eng._remap_width, jnp.int32)
        same = jnp.full(eng._remap_width, eng.P, jnp.int32)
        eng.state = eng._remap_copy(eng.state, idx, idx, same, same)
        eng.state = eng._set_recovery(eng.state, jnp.int32(0), 0.0, 0, 0, 0)
        eng.state = eng._rewind(state=eng.state, lane=jnp.int32(0),
                                new_pos=jnp.int32(1))
    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    seen = {e.name[len("PjitFunction("):-1]
            for p in ProfileData.from_file(str(path)).planes
            for line in p.lines for e in line.events
            if e.name.startswith("PjitFunction(")}
    assert set(want.values()) <= seen
    for attr, name in want.items():
        assert getattr(eng, attr).__name__ == name


# ---- the reader, on a capture built by hand ---- #
MS = 1e6


def _built():
    """A 100 ms window: two plain steps and one tick step, with the device
    busy for 10 ms in each step's decode."""
    sp = []

    def add(name, t0, t1, **stats):
        sp.append(S.Span(name, t0 * MS, (t1 - t0) * MS, "w", stats))
    add("engine.step", 0, 20, boundary=0)
    add("engine.decode", 5, 8)
    add("engine.step", 20, 80, boundary=1)
    add("engine.tick", 22, 70, lanes=2)
    add("engine.pull_lanes", 22, 30, bytes=4e6, lanes=2)
    add("engine.unpack", 30, 32, bytes=1e6)
    add("kv.tick", 32, 50)
    add("engine.push_lanes", 50, 66, bytes=8e6, lanes=2, kv=1)
    add("engine.push_lanes", 66, 68, bytes=1e3, lanes=2, kv=0)
    add("engine.step", 80, 96, boundary=0)
    add("engine.step", 150, 160, boundary=0)   # after the window: ignored
    busy = [(8 * MS, 18 * MS), (70 * MS, 80 * MS), (86 * MS, 90 * MS)]
    return sp, busy, (0.0, 100 * MS)


def test_c_idle_split_across_the_innermost_spans():
    spans, busy, window = _built()
    idle = S.idle_by_span(spans, busy, window)
    assert sum(idle.values()) == pytest.approx(0.076)      # 100 - 24 ms
    assert idle["engine.decode"] == pytest.approx(0.003)
    # [0,5) [18,20) [20,22) [80,86) [90,96)
    assert idle["engine.step"] == pytest.approx(0.021)
    assert idle["engine.pull_lanes"] == pytest.approx(0.008)
    assert idle["kv.tick"] == pytest.approx(0.018)
    assert idle["engine.push_lanes"] == pytest.approx(0.018)
    assert idle["engine.tick"] == pytest.approx(0.002)     # [68, 70)
    assert idle[S.OUTSIDE] == pytest.approx(0.004)         # [96, 100)


def test_c_the_five_numbers():
    spans, busy, window = _built()
    out = S.summary(spans, busy, window)
    assert out["tick_ms"] == pytest.approx(48.0)
    assert out["kv_tick_ms"] == pytest.approx(18.0)
    assert out["lane_pull_gbps"] == pytest.approx(4e6 / 8e6)
    assert out["lane_push_gbps"] == pytest.approx(8e6 / 16e6)
    assert out["plain_step_ms"] == pytest.approx(18.0)      # 20 and 16 ms


def test_c_a_saved_capture_reads_back(tmp_path):
    spans, busy, window = _built()
    S.save(str(tmp_path / "c.json"), spans, busy, window)
    back = S.load(str(tmp_path / "c.json"))
    assert back[0] == spans[:-1] and back[2] == window
    assert S.summary(*back) == S.summary(spans, busy, window)


def test_d_nothing_to_read_gives_no_numbers():
    out = S.summary([], [], (0.0, 1e9))
    for k in ("tick_ms", "kv_tick_ms", "lane_pull_gbps", "lane_push_gbps",
              "plain_step_ms"):
        assert out[k] is None, k
    assert out["idle_by_span"] == {S.OUTSIDE: pytest.approx(1.0)}


def test_c_a_recorded_capture():
    """A recorded chip capture (``tests/data/spans_excerpt.json``, see its
    ``meta``): 6.6 s of the long-context cell around one boundary tick,
    the program's spans on the serve loop's thread and the scheduler's
    worker thread, and the device's busy intervals."""
    rec = json.loads((DATA / "spans_excerpt.json").read_text())
    spans, busy, window = S.load(str(DATA / "spans_excerpt.json"))
    assert {s.name for s in spans} <= PROGRAM_SPANS
    assert len({s.thread for s in spans}) >= 2
    idle = S.idle_by_span(spans, busy, window)
    busy_s = sum(e - s for s, e in busy) / 1e9
    assert 0 < busy_s
    assert sum(idle.values()) == pytest.approx(
        (window[1] - window[0]) / 1e9 - busy_s)
    # the tick's three parts hold most of the idle time, and the spans
    # leave almost none of it uncovered
    tick_parts = (idle["engine.pull_lanes"] + idle["kv.tick"]
                  + idle["engine.push_lanes"])
    assert tick_parts > 0.8 * sum(idle.values())
    assert idle[S.OUTSIDE] < 0.01 * sum(idle.values())
    ticks = [s for s in spans if s.name == "engine.tick"]
    steps = [s for s in spans if s.name == "engine.step"]
    assert len(ticks) == 1
    for t in ticks:
        step = next(s for s in steps if _inside(t, s))
        assert step.stats["boundary"] == t.stats["lanes"] >= 1
        for n in ("engine.pull_lanes", "kv.tick", "engine.push_lanes"):
            assert any(s.name == n and _inside(s, t) for s in spans), n
    # a pull moves the whole pool, all n_lanes columns of every field
    e = rec["meta"]["engine"]
    slots = e["L"] * e["n_lanes"] * e["P_total"]
    kv = 2 * slots * e["page"] * e["kv_heads"] * e["head_dim"] \
        * e["dtype_bytes"]
    meta = slots * (4 + e["page"] + 4 + 2 * e["kv_heads"] * 4   # pt, mask,
                    + 4 + 4 + 1 + 4)           # quant, scales, c, d, fz, at
    pulls = [s for s in spans if s.name == "engine.pull_lanes"]
    assert {s.stats["bytes"] for s in pulls} == {kv + meta}
    out = S.summary(spans, busy, window)
    assert out["tick_ms"] == pytest.approx(
        sum(t.dur for t in ticks) / len(ticks) / 1e6)
    assert out["lane_pull_gbps"] == pytest.approx(
        sum(s.stats["bytes"] for s in pulls) / sum(s.dur for s in pulls))
    plain = [s for s in steps if not any(_inside(t, s) for t in ticks)]
    assert len(plain) == len(steps) - 1 >= 2
    assert out["plain_step_ms"] == pytest.approx(
        sum(s.dur for s in plain) / len(plain) / 1e6)
    for k in ("kv_tick_ms", "lane_push_gbps"):
        assert out[k] > 0, k


def test_the_command_line_prints_the_largest_idle_parts(capsys, tmp_path):
    spans, busy, window = S.load(str(DATA / "spans_excerpt.json"))
    out = S.main([str(DATA / "spans_excerpt.json"),
                  "--save", str(tmp_path / "again.json")])
    assert json.loads(capsys.readouterr().out) == out
    assert len(out["idle_by_span"]) == 12
    assert out["idle_s"] == pytest.approx(sum(
        S.idle_by_span(spans, busy, window).values()))
    assert S.load(str(tmp_path / "again.json")) == (spans, busy, window)
