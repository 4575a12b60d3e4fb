"""The serving path's ``repro:`` spans in a profiler capture, and where
they put the device's idle time.

Each phase of a serving step runs inside a ``jax.profiler.TraceAnnotation``
named ``repro:<layer>.<phase>`` (docs/serving.md, "Spans in a profiler
capture").  With a profiler running they land on the capture's host
planes, on the clock of the device's ``XLA Ops`` events, and their
keyword metadata (``bytes=``, ``lanes=``, ...) comes back as the event's
stats.  This module reads them back and reduces them:

    python -m repro.analysis.spans <log_dir> [--save PATH]

prints, for the capture's window, the device's idle time split across
the innermost span open in each part of it (the 12 largest parts), and
the numbers below.
``--save`` writes the window's spans and device busy intervals as JSON,
which ``load`` reads back like a capture.

Times are nanoseconds on the capture's clock; a span's name drops the
``repro:`` prefix.
"""
from __future__ import annotations

import argparse
import collections
import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

PREFIX = "repro:"
DEVICE_PLANE = re.compile(r"^/device:TPU:0$")
OPS_LINE = "XLA Ops"
WINDOW = "bench:window"       # the benchmark's measured window, if present
OUTSIDE = "outside spans"

Span = collections.namedtuple("Span", "name start dur thread stats")
Interval = Tuple[float, float]


def load(path: str) -> Tuple[List[Span], List[Interval], Interval]:
    """``(spans, device busy intervals, window)`` of a capture: the newest
    ``.xplane.pb`` under the directory ``path``, or a file ``--save``
    wrote.  The window is the ``bench:window`` span if the capture has
    one, else the extent of the ``repro:`` spans."""
    if os.path.isfile(path) and path.endswith(".json"):
        with open(path) as f:
            rec = json.load(f)
        return ([Span(*s) for s in rec["spans"]],
                [tuple(b) for b in rec["busy"]], tuple(rec["window"]))
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    spans: List[Span] = []
    ops: List[Interval] = []
    window: Optional[Interval] = None
    for plane in ProfileData.from_file(found[-1]).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for i, line in enumerate(plane.lines):
            if device and line.name != OPS_LINE:
                continue
            thread = f"{line.name}/{i}"     # threads may share a name
            for e in line.events:
                s, d = float(e.start_ns), float(e.duration_ns)
                if device:
                    ops.append((s, s + d))
                elif e.name.startswith(PREFIX):
                    spans.append(Span(e.name[len(PREFIX):], s, d, thread,
                                      dict(e.stats)))
                elif e.name == WINDOW:
                    window = (s, s + d)
    spans.sort(key=lambda x: x.start)
    if window is None and spans:
        window = (spans[0].start, max(x.start + x.dur for x in spans))
    return spans, union(ops), window or (0.0, 0.0)


def save(path: str, spans: Sequence[Span], busy: Sequence[Interval],
         window: Interval, meta: Optional[Dict] = None) -> None:
    """Write the spans that start in ``window`` and the device's busy
    intervals clipped to it, as JSON that ``load`` reads back."""
    lo, hi = window
    with open(path, "w") as f:
        json.dump({"meta": meta or {}, "window": [lo, hi],
                   "spans": [list(s) for s in within(spans, window)],
                   "busy": union(busy, lo, hi)}, f)


def union(intervals: Iterable[Interval], lo: float = -float("inf"),
          hi: float = float("inf")) -> List[Interval]:
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


def within(spans: Sequence[Span], window: Interval) -> List[Span]:
    """The spans that start inside ``window``."""
    lo, hi = window
    return [s for s in spans if lo <= s.start < hi]


def innermost(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """The timeline of the innermost (shortest) span open at each moment:
    ``(start, end, name)`` pieces, in order, where any span is open."""
    edges = sorted({t for s in spans for t in (s.start, s.start + s.dur)})
    starts = sorted(spans, key=lambda s: s.start)
    out: List[Tuple[float, float, str]] = []
    open_: List[Span] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(starts) and starts[i].start <= a:
            open_.append(starts[i])
            i += 1
        open_ = [s for s in open_ if s.start + s.dur > a]
        if open_:
            name = min(open_, key=lambda s: s.dur).name
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def idle_by_span(spans: Sequence[Span], busy: Sequence[Interval],
                 window: Interval) -> Dict[str, float]:
    """Seconds of the device's idle time in ``window`` under each span:
    every idle stretch is cut where the innermost open span changes, and
    each piece goes to that span, or to "outside spans".  The values sum
    to the window's idle time."""
    lo, hi = window
    edges = [lo] + [t for iv in union(busy, lo, hi) for t in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
    out: Dict[str, float] = collections.defaultdict(float)
    pieces = innermost(spans)
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b = max(pieces[k][0], s), min(pieces[k][1], e)
            if b > a:
                out[pieces[k][2]] += (b - a) / 1e9
                covered += b - a
            k += 1
        out[OUTSIDE] += (e - s - covered) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ---- the numbers a capture gives (None where it holds no such span) ---- #
def _named(spans, window, name):
    return [s for s in within(spans, window) if s.name == name]


def mean_ms(spans: Sequence[Span], window: Interval, name: str
            ) -> Optional[float]:
    """Mean duration of the span ``name``, ms."""
    got = _named(spans, window, name)
    return sum(s.dur for s in got) / len(got) / 1e6 if got else None


def gbps(spans: Sequence[Span], window: Interval, name: str,
         **match) -> Optional[float]:
    """Bytes moved per second inside the span ``name`` (its ``bytes``
    stat over its duration, summed over the spans whose stats equal
    ``match``), GB/s."""
    got = [s for s in _named(spans, window, name)
           if all(s.stats.get(k) == v for k, v in match.items())]
    t = sum(s.dur for s in got)                  # bytes per ns = GB/s
    return sum(s.stats["bytes"] for s in got) / t if got and t else None


def plain_step_ms(spans: Sequence[Span], window: Interval
                  ) -> Optional[float]:
    """Mean duration of the engine steps that hold no boundary tick, ms."""
    ticks = [s for s in spans if s.name == "engine.tick"]

    def holds_tick(step):
        return any(t.thread == step.thread and step.start <= t.start
                   < step.start + step.dur for t in ticks)
    got = [s for s in _named(spans, window, "engine.step")
           if not holds_tick(s)]
    return sum(s.dur for s in got) / len(got) / 1e6 if got else None


def summary(spans: Sequence[Span], busy: Sequence[Interval],
            window: Interval) -> Dict:
    """The numbers of a capture's window, by the name a metric would
    give them."""
    return {
        "tick_ms": mean_ms(spans, window, "engine.tick"),
        "kv_tick_ms": mean_ms(spans, window, "kv.tick"),
        "lane_pull_gbps": gbps(spans, window, "engine.pull_lanes"),
        "lane_push_gbps": gbps(spans, window, "engine.push_lanes", kv=1),
        "plain_step_ms": plain_step_ms(spans, window),
        "tick_parts_ms": {n: mean_ms(spans, window, n) for n in (
            "engine.pull_lanes", "engine.unpack", "kv.tick",
            "engine.push_lanes", "engine.remap")},
        "idle_by_span": idle_by_span(spans, busy, window),
    }


def main(argv=None) -> Dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="a profiler log directory, or a file "
                                 "--save wrote")
    ap.add_argument("--save", default=None,
                    help="write the window's spans and busy intervals here")
    args = ap.parse_args(argv)
    spans, busy, window = load(args.path)
    out = summary(spans, busy, window)
    idle = out["idle_by_span"]
    out["idle_by_span"] = dict(list(idle.items())[:12])
    out["window_s"] = (window[1] - window[0]) / 1e9
    out["idle_s"] = sum(idle.values())
    if args.save:
        save(args.save, spans, busy, window)
    print(json.dumps(out, indent=1))
    return out


if __name__ == "__main__":
    main()
