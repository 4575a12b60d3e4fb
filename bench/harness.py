"""One benchmark run: a cell of ``BENCHMARK.json`` on the chip, end to end.

Everything a cell needs is found by name:

  bench/configs/<config>.json    the model configuration as run
  bench/archs/<arch>.py          what its published keys mean: the
                                 program's ``ModelConfig`` and the counts
                                 ``bench/work`` needs (``architecture``)
  bench/references/<arch>.py     its plain reference (``architecture``)
  bench/traffic/<traffic>.json   the traffic mix and serving settings
  bench/metrics/<metric>.py      one per-layer metric's reader
  bench/work/<call>.py           operations and bytes a call needs
  bench/limits/<cell>.json       the limits that decide ``correct``
  bench/peaks.json               the chip's peaks, by device kind

The system under test is built the way ``launch/serve.py`` builds it
(``model_config`` -> ``init_model`` -> ``build_engine`` -> ``Scheduler``)
and driven through ``AsyncServingEngine.submit``; client-side timestamps
come from the coroutines that read each request's event stream.
"""
from __future__ import annotations

import asyncio
import dataclasses
import functools
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import time
import types
from typing import Any, Dict, List, Optional

import numpy as np

import traffic
import window as W

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
OK_STATUS = ("completed",)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def read_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    root: pathlib.Path  # the checkout whose BENCHMARK.json names the cell
    name: str
    spec: Dict          # the BENCHMARK.json workload entry
    bench: Dict         # the whole BENCHMARK.json
    config: Dict        # bench/configs/<config>.json
    mix: Dict           # bench/traffic/<traffic>.json
    limits: Optional[Dict]

    @property
    def chips(self) -> int:
        return self.spec["chips"]

    def metrics(self, kind: str) -> List[Dict]:
        """The cell's end_to_end or per_layer metrics."""
        out = []
        e2e = {m["name"] for m in self.cell_e2e()}
        for m in self.bench[kind]:
            if "workloads" in m:
                if self.name in m["workloads"]:
                    out.append(m)
            elif kind == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out

    def cell_e2e(self) -> List[Dict]:
        return [m for m in self.bench["end_to_end"]
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = read_json(root / "BENCHMARK.json")
    specs = {w["name"]: w for w in bench["workloads"]}
    if name not in specs:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(specs)}")
    spec = specs[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[spec["config"]]
    lim = root / "bench" / "limits" / f"{name}.json"
    return Cell(root=root, name=name, spec=spec, bench=bench,
                config=read_json(root / cfg_entry["file"]),
                mix=read_json(root / "bench" / "traffic"
                              / f"{spec['traffic']}.json"),
                limits=read_json(lim) if lim.exists() else None)


def weight_seed(seed: int) -> int:
    """A 31-bit key for the weights, from a seed of any size."""
    return int(np.random.SeedSequence([int(seed), 7]).generate_state(1)[0]
               & 0x7FFFFFFF)


# ------------------------------------------------------------------ #
# device and build
# ------------------------------------------------------------------ #
def check_device(chips: int) -> Dict[str, Any]:
    """The devices JAX reports; exits unless they are TPUs, as many as
    the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"bench: needs {chips} TPU chip(s), but JAX reports "
            f"{len(devs)} device(s) on platform {devs[0].platform!r} "
            f"({devs[0].device_kind})")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class CompileClock:
    """Compilations (or persistent-cache fetches) JAX reports, from its
    own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.n += 1


def arch(c: Dict, root: pathlib.Path = ROOT):
    """The architecture file the configuration ``c`` names
    (``bench/archs/<architecture>.py`` under the checkout ``root``): the
    only reader of a published configuration's own keys, for the program
    and for the work counts."""
    path = root / "bench" / "archs" / f"{c.get('architecture')}.py"
    if not path.is_file():
        raise SystemExit(f"bench: configuration {c.get('name')!r} needs the "
                         f"architecture file {path}, which does not exist")
    return _load_arch(path)


@functools.lru_cache(maxsize=None)
def _load_arch(path: pathlib.Path):
    return load_module(path)


def model_config(c: Dict, root: pathlib.Path = ROOT):
    """The ``ModelConfig`` a configuration file describes, built by its
    architecture file, with the freeze schedule ``launch/serve.py``'s
    ``model_config`` gives its serve_arch."""
    from repro.launch import serve
    freeze = serve.model_config(c["serve_arch"]).freeze
    return arch(c, root).model_config(c, freeze)


def make_params(c: Dict, cfg, seed: int):
    """The served model's weights, made on the device in one jitted call
    from ``seed`` in the type they are served in: every matrix normal with
    the configuration's published ``initializer_range`` as its standard
    deviation (the Hugging Face initialisation of these models), every
    norm scale one (the program stores it as an offset from one).  One key
    per parameter tensor, in the order of the flattened tree."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as MD
    shapes = jax.eval_shape(lambda k: MD.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    std = c["initializer_range"]

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (path, s) in zip(keys, flat):
            if "norm" in jax.tree_util.keystr(path):
                out.append(jnp.zeros(s.shape, s.dtype))
            else:
                out.append((jax.random.normal(k, s.shape, jnp.float32)
                            * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)
    return jax.jit(make)(jax.random.PRNGKey(seed))


def serving_config(mix: Dict, seed: int):
    from repro.serving.config import ServingConfig
    s = mix["serving"]
    return ServingConfig(max_seq=s["max_seq"], n_lanes=s["n_lanes"],
                         max_active_pages=s["max_active_pages"],
                         prefill_chunk=s["prefill_chunk"],
                         enable_freeze=s["enable_freeze"],
                         seed=weight_seed(seed))


def sampling(mix: Dict, greedy: bool):
    from repro.serving.sampling import SamplingParams
    return SamplingParams.greedy() if greedy \
        else SamplingParams(temperature=mix["temperature"])


def warm(eng, sched, mix: Dict, plan: List[traffic.Planned]) -> None:
    """Compile every program the cell's traffic runs: the prefill chunks
    of each prompt length it sends, then one greedy and one sampled
    request through admission, install, decode, page-boundary ticks and
    both samplers; and the programs that only rare events reach (staging
    writes and remaps, rewinds) as no-ops on an empty engine."""
    import jax.numpy as jnp
    lens = sorted({(len(p.prompt), p.n_tokens) for p in plan})
    for n in sorted({x for x, _ in lens}):
        eng.warm_prefill(n, max(t for x, t in lens if x == n))
    L = eng.L_attn
    eng.state = eng._rewind(state=eng.state, lane=jnp.int32(0),
                            new_pos=jnp.int32(1))
    if eng.S_stage:
        page, kvh, hd = eng.state.k.shape[3:]
        z = np.zeros((L, page, kvh, hd), np.dtype(eng.state.k.dtype))
        eng.state = eng._stage_write(
            eng.state, jnp.int32(0), jnp.asarray(np.full(L, -1, np.int32)),
            jnp.asarray(z), jnp.asarray(z), jnp.asarray(np.zeros(L, bool)))
        W_ = eng._remap_width
        idx = jnp.asarray(np.zeros(W_, np.int32))
        self_copy = jnp.asarray(np.full(W_, eng.P, np.int32))
        eng.state = eng._remap_copy(eng.state, idx, idx, self_copy,
                                    self_copy)
    rng = traffic.rng_for(0, 99)
    # every prompt length once through admission and install (the
    # install slices each length's scratch cache), and the shortest
    # greedily and sampled across one page boundary
    n_new = eng.page + 2
    shortest = min(len(p.prompt) for p in plan)
    warm_reqs = [(n, 2, True) for n in sorted({len(p.prompt) for p in plan})
                 if n != shortest]
    warm_reqs += [(shortest, n_new, True), (shortest, n_new, False)]
    for n, t, greedy in warm_reqs:
        prompt = rng.integers(0, eng.cfg.vocab_size, n, dtype=np.int32)
        sched.submit(prompt, t, sampling(mix, greedy))
    sched.run()
    sched.done.clear()
    sched.metrics.clear()


# ------------------------------------------------------------------ #
# the measured window
# ------------------------------------------------------------------ #
@dataclasses.dataclass
class Run:
    recs: List[W.Record]
    t0: float = 0.0                  # window start (host clock)
    t1: float = 0.0                  # window end
    before: Dict[str, float] = dataclasses.field(default_factory=dict)
    after: Dict[str, float] = dataclasses.field(default_factory=dict)
    steps: List[Any] = dataclasses.field(default_factory=list)
                                     # (start, end) of each Scheduler.step
    lane_steps: int = 0              # decode lanes summed over steps
    visible: float = 0.0             # visible tokens, summed over layers,
                                     # lanes and steps
    trace: Any = None


def counters(eng) -> Dict[str, float]:
    return {"wall_step": eng.wall_step, "swap_out": eng.ctl.n_swap_out,
            "swap_in": eng.ctl.n_swap_in, "blocked_s": eng.stats.blocked_s}


class Instrument:
    """Spans from the harness around the calls into each layer, on in
    traced runs only: a ``bench:<name>`` TraceAnnotation around
    ``Scheduler.step`` and the engine's step, boundary tick, transfers,
    prefill chunk and install, plus the per-step samples the per-layer
    metrics read (time in ``Scheduler.step``, decode lanes, visible
    tokens)."""

    ENGINE = ("step_once", "_boundary_tick", "_pull_lanes", "_push_lanes",
              "_prefill_tick", "_install", "_drain_ring")

    def __init__(self, sched):
        import jax
        self.run: Optional[Run] = None
        self.active = False
        eng = sched.engine
        self.seen: Dict[int, int] = {}

        def wrap(obj, name):
            fn = getattr(obj, name)
            ann = jax.profiler.TraceAnnotation

            def wrapped(*a, **k):
                with ann("bench:" + name.lstrip("_")):
                    return fn(*a, **k)
            setattr(obj, name, wrapped)

        for name in self.ENGINE:
            wrap(eng, name)
        step = sched.step

        def timed_step():
            if not self.active:
                return step()
            lanes = [i for i, l in enumerate(eng.lanes)
                     if l.request is not None
                     and (i not in eng.prefills or eng.prefills[i].over)]
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench:Scheduler.step"):
                out = step()
            self.run.steps.append((t, time.perf_counter()))
            self.run.lane_steps += len(lanes)
            for l in eng.lanes:
                req = l.request
                if req is None or req.telemetry is None:
                    continue
                act = req.telemetry.active_kv
                k = self.seen.get(req.uid, 0)
                self.run.visible += sum(act[k:]) * eng.L_attn
                self.seen[req.uid] = len(act)
            return out
        sched.step = timed_step


class Visibility:
    """The pages each decode step attended, for the check.  Just before
    every paged decode step, a jitted snapshot of the step's page table
    with frozen slots struck out (-1) is taken on the device and copied
    to the host behind the step; for each live lane the snapshot is filed
    under (request id, position of the token the step reads).  Only which
    logical pages each layer saw is taken: the check recomputes every key,
    value and token itself, so a step that fails to write its keys, or a
    page brought back with the wrong contents, still shows."""

    def __init__(self, eng):
        import jax
        import jax.numpy as jnp
        self.page = eng.page
        self.rows: Dict[Any, Any] = {}   # (uid, pos) -> (snapshot, lane)
        self.snaps: List[Optional[np.ndarray]] = []
        self._pending: Optional[Any] = None
        snap = jax.jit(lambda pt, frozen: jnp.where(frozen, -1, pt))
        step = eng._step

        def wrapped(*a, **k):
            self.land()
            s = snap(k["state"].page_table, k["state"].freeze.frozen)
            s.copy_to_host_async()
            self._pending = (len(self.snaps), s)
            self.snaps.append(None)
            for i, l in enumerate(eng.lanes):
                if l.request is not None and (
                        i not in eng.prefills or eng.prefills[i].over):
                    self.rows[(l.request.uid, int(eng.pos[i]))] = \
                        (len(self.snaps) - 1, i)
            return step(*a, **k)
        eng._step = wrapped

    def land(self) -> None:
        """Bring the last snapshot to the host."""
        if self._pending is not None:
            i, s = self._pending
            self.snaps[i] = np.asarray(s)
            self._pending = None

    def reset(self) -> None:
        self.land()
        self.rows.clear()
        self.snaps.clear()

    def pages(self, uid: int, pos: int) -> np.ndarray:
        """(L, pages) boolean: the logical pages each layer attended in
        the step that read the token at ``pos`` of request ``uid``."""
        idx, lane = self.rows[(uid, pos)]
        pt = self.snaps[idx][:, lane]                      # (L, P)
        out = np.zeros((pt.shape[0], pos // self.page + 1), bool)
        for l, row in enumerate(pt):
            row = row[(row >= 0) & (row < out.shape[1])]
            out[l, row] = True
        return out


async def drive(aeng, eng, mix: Dict, plan: List[traffic.Planned],
                seconds: float, trace: bool, inst: Optional[Instrument]
                ) -> Run:
    """Send the plan's requests, measure ``seconds``, stop the serve loop.
    Closed loop: each client's first request is admitted in set-up,
    ``prebuild_wave`` clients at a time, and the window opens once every
    client has its first token.  Open loop: arrivals start at once and the
    window opens ``warm_s`` later."""
    import jax
    recs = [W.Record(p.index, p.prompt, p.n_tokens, p.greedy) for p in plan]
    run = Run(recs)
    if inst is not None:
        inst.run = run
    tasks: List[asyncio.Task] = []
    loop = asyncio.get_running_loop()
    now = time.perf_counter
    first_token: Dict[int, asyncio.Future] = {}

    async def client(rec: W.Record) -> None:
        rec.sent = now()
        stream = await aeng.submit(rec.prompt, rec.n_tokens,
                                   sampling(mix, rec.greedy))
        rec.uid = stream.uid
        fut = first_token.get(rec.index)
        async for ev in stream:
            t = now()
            if ev["event"] == "token":
                rec.times.append(t)
                rec.tokens.append(ev["token"])
                if fut is not None and not fut.done():
                    fut.set_result(t)
            elif ev["event"] == "rewind":
                rec.rewinds.append((t, len(rec.tokens) - ev["to"]))
                del rec.tokens[ev["to"]:]
            else:
                rec.done, rec.status = t, ev["status"]
        if mix["loop"] == "closed":
            nxt = rec.index + mix["clients"]
            if nxt < len(recs):
                tasks.append(loop.create_task(client(recs[nxt])))

    def spawn(rec):
        tasks.append(loop.create_task(client(rec)))

    async def settled(futs) -> None:
        while not all(f.done() for f in futs):
            if aeng.last_exception is not None:
                raise aeng.last_exception
            await asyncio.sleep(0.02)

    await aeng.start()
    if mix["loop"] == "closed":
        wave = mix.get("prebuild_wave", mix["clients"])
        firsts = recs[:mix["clients"]]
        for i in range(0, len(firsts), wave):
            group = firsts[i:i + wave]
            for r in group:
                first_token[r.index] = loop.create_future()
                spawn(r)
            await settled([first_token[r.index] for r in group])
        t_start = now()
    else:
        t_stream = now()
        t_start = t_stream + mix["warm_s"]
        for r in recs:
            r.due = t_stream + plan[r.index].due_s

        async def generator():
            for r in recs:
                delay = r.due - now()
                if delay > 0:
                    await asyncio.sleep(delay)
                spawn(r)
        gen = loop.create_task(generator())
        await asyncio.sleep(max(t_start - now(), 0))
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        import tracing
        tracing.start(str(TRACE_DIR))
        win = jax.profiler.TraceAnnotation(tracing.WINDOW)
        win.__enter__()
    run.before = counters(eng)
    if inst is not None:
        inst.active = True
    run.t0 = now()
    await asyncio.sleep(seconds)
    run.t1 = now()
    if inst is not None:
        inst.active = False
    run.after = counters(eng)
    if trace:
        win.__exit__(None, None, None)
        tracing.stop()
    if mix["loop"] == "open":
        gen.cancel()
    await aeng.close()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    if aeng.last_exception is not None:
        raise aeng.last_exception
    return run


# ------------------------------------------------------------------ #
# metrics
# ------------------------------------------------------------------ #
def end_to_end(cell: Cell, run: Run, setup_s: float, peak: int
               ) -> Dict[str, Dict]:
    t0, t1 = run.t0, run.t1
    vals = {
        "decode_tok_s": W.decode_tok_s(run.recs, t0, t1),
        "itl_p95_ms": _scale(W.percentile(W.itl_gaps(run.recs, t0, t1), 95),
                             1e3),
        "ttft_p95_s": W.percentile(W.ttfts(run.recs, t0, t1), 95),
        "peak_hbm_gb": peak / 1e9,
        "setup_s": setup_s,
    }
    out = {}
    for m in cell.cell_e2e():
        v = vals.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def _scale(v: Optional[float], k: float) -> Optional[float]:
    return None if v is None else v * k


def per_layer(cell: Cell, run: Run, eng_info: Dict) -> Dict[str, Dict]:
    ctx = types.SimpleNamespace(
        run=run, window_s=run.t1 - run.t0, cell=cell, config=cell.config,
        mix=cell.mix, engine=eng_info, trace=run.trace,
        peaks=peaks(eng_info["device_kind"]), work=work_modules(), W=W)
    out = {}
    for m in cell.metrics("per_layer"):
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def peaks(kind: str) -> Dict[str, float]:
    table = read_json(BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def work_modules():
    return types.SimpleNamespace(**{
        p.stem: load_module(p) for p in sorted((BENCH / "work").glob("*.py"))})


# ------------------------------------------------------------------ #
# correctness
# ------------------------------------------------------------------ #
def check_sample(cell: Cell, run: Run, seed: int) -> List[W.Record]:
    """Greedy requests with served tokens, drawn from the seed, the one
    with the most served tokens always among them."""
    ok = [r for r in run.recs if r.greedy and r.tokens]
    if not ok:
        return []
    k = cell.mix["check"]["requests"]
    longest = max(ok, key=lambda r: (len(r.tokens), -r.index))
    rest = [r for r in ok if r is not longest]
    rng = traffic.rng_for(seed, 2)
    pick = [rest[i] for i in sorted(rng.permutation(len(rest))[:k - 1])]
    return [longest] + pick


def compared(r: W.Record, vis: Visibility):
    """Every served token of ``r``, as (input sequence, positions, pages,
    tokens): token 0, sampled from the prefill, at the prompt's last
    position with full causal attention; token k > 0 at the position of
    the token its decode step read, attending, per layer, the pages that
    step saw (``pages``: index in positions -> (L, n) boolean)."""
    n = len(r.tokens)
    sp = len(r.prompt)
    seq = np.concatenate([r.prompt, np.asarray(r.tokens[:n - 1], np.int32)])
    pos = np.arange(sp - 1, sp - 1 + n)
    pages = {k: vis.pages(r.uid, int(pos[k])) for k in range(1, n)}
    return seq, pos, pages, np.asarray(r.tokens, np.int64)


def reference_gaps(cell: Cell, sample: List[W.Record], vis: Visibility,
                   seed: int, control: bool) -> Dict[str, Any]:
    """The widest and the mean gap by which a compared served token's
    logit lies below the reference's best.  With ``control``, the int8 control is put in
    the program's place at the same positions, with the same pages, and
    the gap of the token it puts first is read the same way
    (``control``: its numbers, under the same names)."""
    ref_mod = load_module(BENCH / "references"
                          / f"{cell.config['architecture']}.py")
    items = [compared(r, vis) for r in sample]
    s_max = max(len(seq) for seq, *_ in items)
    wseed = weight_seed(seed)
    n_tok = int(sum(len(t) for *_, t in items))
    looks = [toks[:, None] for *_, toks in items]
    if control:
        ctl = ref_mod.Reference(cell.config, wseed, s_max, page=vis.page,
                                int8=True)
        for j, (seq, pos, pages, toks) in enumerate(items):
            _, top, _ = ctl.score(seq, pos, np.zeros((len(pos), 1)), pages)
            looks[j] = np.stack([toks, top], axis=1)
        ctl.free()
        del ctl
        gc.collect()
    ref = ref_mod.Reference(cell.config, wseed, s_max, page=vis.page)
    gaps: List[List[np.ndarray]] = [[], []]
    for (seq, pos, pages, _), look in zip(items, looks):
        best, _, picked = ref.score(seq, pos, look, pages)
        for col in range(look.shape[1]):
            gaps[col].append(best - picked[:, col])
    ref.free()

    def summary(g: List[np.ndarray]) -> Dict[str, Any]:
        flat = np.concatenate(g)
        j = int(np.argmax([x.max() for x in g]))
        return {"requests": len(sample), "tokens": n_tok,
                "max_gap": float(flat.max()),
                "mean_gap": float(flat.mean()),
                "max_gap_first": float(max(x[0] for x in g)),
                "max_gap_decode": float(max((x[1:].max() for x in g
                                             if len(x) > 1), default=0.0)),
                # where the widest gap sits (request, token index), and the
                # next widest, to tell one outlier from a shifted spread
                "widest_at": [sample[j].index, int(np.argmax(g[j]))],
                "top_gaps": [float(v) for v in np.sort(flat)[::-1][:5]],
                "n_flipped": int((flat > 0).sum())}
    out = summary(gaps[0])
    if control:
        out["control"] = summary(gaps[1])
    return out


def judge(cell: Cell, found: Dict[str, Any]) -> (bool, Dict[str, Dict]):
    """Each compared number beside its limit; correct iff all within."""
    limits = (cell.limits or {}).get("limits", {})
    checks = {}
    ok = bool(limits) and found.get("tokens", 0) > 0
    for name, lim in limits.items():
        v = found.get(name)
        checks[name] = {"value": v, "limit": lim}
        ok = ok and v is not None and v <= lim
    if not limits:
        checks["max_gap"] = {"value": found.get("max_gap"), "limit": None}
    return ok, checks
