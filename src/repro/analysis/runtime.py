"""trace_guard — assert jit compile caches stay flat over a workload.

A jitted callable's ``_cache_size()`` counts the traces it has compiled;
steady-state serving must not grow it (every retrace stalls a step on
XLA compilation, the exact pathology the ROADMAP's async-latency item
blames).  The guard snapshots every trackable jit before and after a
``with`` block::

    with trace_guard(engine, label="timed region") as tg:
        for _ in range(steps):
            engine.step_once()
    report["n_retraces"] = tg.n_retraces          # 0 when warm

Targets may be jitted callables themselves or objects whose attributes
hold them (the engines: ``self._step``, ``self._chunk``...).  Pass
``max_new_compiles=0`` to raise ``RetraceError`` on any growth instead
of just reporting it — benchmarks report, CI asserts via
``tools/check_bench.py --max-retraces``.

``_cache_size`` is a private jax API (present on the pinned 0.9.0);
callables without it are skipped and listed in ``report.untracked`` so a
jax upgrade degrades this to a no-op rather than an error.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Dict, Iterator, List, Tuple


class RetraceError(RuntimeError):
    """Raised when a guarded region compiled more traces than allowed."""


def _cache_size(fn: Any) -> int:
    try:
        return int(fn._cache_size())
    except Exception:
        return -1


def _discover(targets: Tuple[Any, ...]) -> Tuple[Dict[str, Any], List[str]]:
    """Map label -> jitted callable for every trackable jit reachable from
    ``targets`` (the target itself, or its instance attributes)."""
    tracked: Dict[str, Any] = {}
    untracked: List[str] = []

    def add(label: str, fn: Any) -> None:
        if _cache_size(fn) >= 0:
            base, n = label, 2
            while label in tracked:            # e.g. two engines of a class
                label = f"{base}#{n}"
                n += 1
            tracked[label] = fn
        else:
            untracked.append(label)

    for t in targets:
        if hasattr(t, "_cache_size"):
            add(getattr(t, "__name__", type(t).__name__), t)
            continue
        attrs = vars(t) if hasattr(t, "__dict__") else {}
        found = False
        for name, val in attrs.items():
            if hasattr(val, "_cache_size"):
                add(f"{type(t).__name__}.{name}", val)
                found = True
        if not found:
            untracked.append(type(t).__name__)
    return tracked, untracked


@dataclasses.dataclass
class TraceReport:
    label: str
    before: Dict[str, int]
    after: Dict[str, int] = dataclasses.field(default_factory=dict)
    untracked: List[str] = dataclasses.field(default_factory=list)
    _fns: Dict[str, Any] = dataclasses.field(default_factory=dict, repr=False)

    @property
    def growth(self) -> Dict[str, int]:
        """New compiles per jit over the guarded region (grown only)."""
        return {k: self.after.get(k, v) - v
                for k, v in self.before.items()
                if self.after.get(k, v) != v}

    @property
    def n_retraces(self) -> int:
        return sum(self.growth.values())

    def summary(self) -> Dict[str, Any]:
        return {"label": self.label, "n_retraces": self.n_retraces,
                "growth": self.growth, "n_tracked": len(self.before),
                "untracked": list(self.untracked)}


@contextlib.contextmanager
def trace_guard(*targets: Any, max_new_compiles: int = None,
                label: str = "") -> Iterator[TraceReport]:
    fns, untracked = _discover(targets)
    report = TraceReport(label=label,
                         before={k: _cache_size(f) for k, f in fns.items()},
                         untracked=untracked, _fns=fns)
    try:
        yield report
    finally:
        report.after = {k: _cache_size(f) for k, f in fns.items()}
    if max_new_compiles is not None and report.n_retraces > max_new_compiles:
        raise RetraceError(
            f"jit compile caches grew by {report.n_retraces} trace(s) "
            f"(allowed {max_new_compiles}) in {label or 'guarded region'}: "
            f"{report.growth}")
