"""Profiler capture of the measured window and its reduction to the
numbers the per-layer metrics read.

A trace is reduced from a flat list of events ``(plane, line, name,
start_ns, dur_ns[, thread, stats])``, so the reduction can be checked on a
small recorded list without a chip.  Device events are those on planes
named ``/device:TPU:<n>``; the harness's own host spans are the events
named ``bench:<span>`` on the host planes, written by
``jax.profiler.TraceAnnotation`` on the same clock.  ``bench:window``
brackets the measured window.

The program's own spans, ``repro:<layer>.<phase>`` on the host planes
(docs/serving.md), are kept with their thread (``<line>/<index>``, as
threads may share a name) and their stats, the keyword metadata the
program gave them (``bytes=``, ``lanes=``, ...): a counter the program
adds reaches a per-layer metric's reader as a span's stat.  The
arithmetic on them is copied from ``repro.analysis.spans``, so the
yardstick stays with the benchmark.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple                   # plane, line, name, start, dur[, thread,
                                # stats] (the last two on program spans)
Span = collections.namedtuple("Span", "name start dur thread stats")

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# the device line whose events are single operations (XLA's op line);
# "XLA Modules" carries whole programs, one event per executed program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench:window"
PROGRAM = "repro:"               # the program's spans


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0     # Python calls would swamp the trace
    opts.host_tracer_level = 2       # keeps TraceAnnotation spans
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def load(log_dir: str) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``log_dir``; the
    program's spans also with their thread and stats."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for i, line in enumerate(plane.lines):
            for e in line.events:
                ev = (plane.name, line.name, short_name(e.name),
                      float(e.start_ns), float(e.duration_ns))
                if not device and e.name.startswith(PROGRAM):
                    ev += (f"{line.name}/{i}", dict(e.stats))
                out.append(ev)
    return out


def short_name(name: str) -> str:
    """A device op's event is named by its whole HLO instruction
    (``%fusion.12 = bf16[...] fusion(...)``); keep the instruction name."""
    return name.split(" = ", 1)[0].lstrip("%")


def excerpt(events: Sequence[Event], ms: float, path: str,
            meta: Dict) -> None:
    """Write the events of the window's first ``ms`` milliseconds (the
    window span cut to that length) with ``meta`` as JSON."""
    import json
    lo = next(s for p, _, n, s, d, *_ in events
              if n == WINDOW and not DEVICE_PLANE.match(p))
    hi = lo + ms * 1e6
    keep = [[p, line, n, s - lo, min(d, hi - s), *rest]
            for p, line, n, s, d, *rest in events
            if lo <= s < hi and n != WINDOW]
    keep.append(["/host:CPU", "python", WINDOW, 0.0, hi - lo])
    with open(path, "w") as f:
        json.dump({"meta": meta, "events": keep}, f)


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
             hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals clipped to [lo, hi]."""
    merged: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Reduced:
    window_ns: Tuple[float, float]
    n_devices: int
    busy_ns: float                          # mean over devices
    gaps: List[Tuple[float, float, str]]    # idle gaps with the host span
    op_ns: Dict[str, float]                 # device time by op name
    op_calls: Dict[str, int]
    module_ns: Dict[str, float]             # device time by program name
    module_calls: Dict[str, int]
    holding: Dict[str, set]                 # program name -> op names run
                                            # inside its executions
    spans: List[Span]                       # the program's spans, by start

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def ops_matching(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of device ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        s = sum(v for k, v in self.op_ns.items() if rx.search(k))
        n = sum(v for k, v in self.op_calls.items() if rx.search(k))
        return s / 1e9, n

    def modules_matching(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of device programs whose name matches."""
        return self._modules(lambda k: re.search(pattern, k))

    def modules_holding(self, op_pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of device programs that ran an op whose name
        matches ``op_pattern`` (programs jitted from a
        ``functools.partial`` are all named ``jit__unknown``, so a program
        is told by what it runs)."""
        rx = re.compile(op_pattern)
        return self._modules(
            lambda k: any(rx.search(o) for o in self.holding.get(k, ())))

    def _modules(self, pick) -> Tuple[float, int]:
        names = [k for k in self.module_ns if pick(k)]
        return (sum(self.module_ns[k] for k in names) / 1e9,
                sum(self.module_calls[k] for k in names))

    # ---- the program's spans that start in the window (None where it
    # holds no such span); as ``repro.analysis.spans`` computes them ----
    def _named(self, name: str) -> List[Span]:
        lo, hi = self.window_ns
        return [s for s in self.spans if s.name == name and lo <= s.start < hi]

    def span_ms(self, name: str) -> Optional[float]:
        """Mean duration of the span ``name``, ms."""
        got = self._named(name)
        return sum(s.dur for s in got) / len(got) / 1e6 if got else None

    def span_gbps(self, name: str, **match) -> Optional[float]:
        """Bytes moved per second inside the span ``name`` (its ``bytes``
        stat over its duration, summed over the spans whose stats equal
        ``match``), GB/s."""
        got = [s for s in self._named(name)
               if all(s.stats.get(k) == v for k, v in match.items())]
        t = sum(s.dur for s in got)              # bytes per ns = GB/s
        return sum(s.stats["bytes"] for s in got) / t if got and t else None

    def plain_step_ms(self) -> Optional[float]:
        """Mean duration of the engine steps that hold no boundary tick,
        ms."""
        ticks = [s for s in self.spans if s.name == "engine.tick"]

        def holds_tick(step):
            return any(t.thread == step.thread and step.start <= t.start
                       < step.start + step.dur for t in ticks)
        got = [s for s in self._named("engine.step") if not holds_tick(s)]
        return sum(s.dur for s in got) / len(got) / 1e6 if got else None

    def breakdown(self, k: int = 10) -> Dict[str, List]:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:k]
        gaps = sorted(self.gaps, key=lambda g: -(g[1] - g[0]))[:k]
        return {"device_ops": [[n, v / 1e9] for n, v in ops],
                "idle_gaps": [[lbl, (e - s) / 1e9] for s, e, lbl in gaps]}


def reduce(events: Sequence[Event]) -> Reduced:
    """Busy union, idle gaps (each labelled with the innermost harness
    span open at its midpoint), and device time by op and by program,
    all within the ``bench:window`` span; and every program span of the
    capture (the capture brackets the window; the span readers take the
    ones that start inside it)."""
    win = [(s, s + d) for p, _, n, s, d, *_ in events
           if n == WINDOW and not DEVICE_PLANE.match(p)]
    if not win:
        raise ValueError("the trace holds no bench:window span")
    lo, hi = win[0]
    devices = sorted({p for p, *_ in events if DEVICE_PLANE.match(p)})
    spans = sorted(((s, s + d, n[len("bench:"):])
                    for p, _, n, s, d, *_ in events
                    if n.startswith("bench:") and n != WINDOW
                    and not DEVICE_PLANE.match(p)), key=lambda x: x[0])
    program = sorted((Span(n[len(PROGRAM):], s, d, *rest)
                      for p, _, n, s, d, *rest in events
                      if rest and n.startswith(PROGRAM)),
                     key=lambda x: x.start)
    op_ns: Dict[str, float] = collections.defaultdict(float)
    op_calls: Dict[str, int] = collections.defaultdict(int)
    mod_ns: Dict[str, float] = collections.defaultdict(float)
    mod_calls: Dict[str, int] = collections.defaultdict(int)
    busy_total = 0.0
    gaps: List[Tuple[float, float, str]] = []
    holding: Dict[str, set] = collections.defaultdict(set)
    for dev in devices:
        ivs = []
        mods = []
        for p, line, n, s, d, *_ in events:
            if p != dev or not (lo <= s < hi):
                continue
            if line == OPS_LINE:
                ivs.append((s, s + d, n))
                op_ns[n] += min(d, hi - s)
                op_calls[n] += 1
            elif line == MODULES_LINE:
                mods.append((s, s + d, n))
                mod_ns[n] += min(d, hi - s)
                mod_calls[n] += 1
        _attribute(sorted(mods), sorted(ivs), holding)
        ivs = [(s, e) for s, e, _ in ivs]
        busy = union_ns(ivs, lo, hi)
        busy_total += sum(e - s for s, e in busy)
        if dev == devices[0]:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((s, e, _span_at(spans, (s + e) / 2)))
    n = max(len(devices), 1)
    return Reduced((lo, hi), len(devices), busy_total / n, gaps,
                   dict(op_ns), dict(op_calls), dict(mod_ns),
                   dict(mod_calls), dict(holding), program)


def _attribute(mods, ops, holding) -> None:
    """Record, for each program, the names of the ops that started inside
    one of its executions (both lists sorted by start)."""
    i = 0
    for s, e, name in mods:
        while i < len(ops) and ops[i][0] < s:
            i += 1
        j = i
        while j < len(ops) and ops[j][0] < e:
            holding[name].add(ops[j][2])
            j += 1


def _span_at(spans: Sequence[Tuple[float, float, str]], t: float) -> str:
    """The shortest harness span containing ``t`` (the innermost one),
    or "outside spans"."""
    best: Optional[Tuple[float, str]] = None
    for s, e, name in spans:
        if s > t:
            break
        if e >= t and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "outside spans"
