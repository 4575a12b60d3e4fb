"""Host<->device transfer pipeline for the serving engines.

The serving hot path used to block on a ``jax.device_get`` every decode
step (tokens + telemetry) and on per-lane pool slices at every page
boundary.  This module provides the three primitives that make the step
loop asynchronous with respect to the host:

* ``TransferStats`` — accounting for every host<->device transfer the
  engine issues, split into *blocking* (the host stalled on data that was
  not already in flight) and *async* (issued early, consumed after the
  device had time to produce it).  Byte counts are the bytes that crossed
  the bus: each array as it was sent.  ``host_blocked_fraction`` — the
  share of engine steps that stalled on at least one blocking transfer —
  is the benchmark's pipeline-health metric: the synchronous path sits at
  1.0 by construction, the async pipeline only blocks at page-boundary ticks.

* ``FetchRing`` — the double-buffered device->host fetch ring.  At step N
  the engine pushes the step's device arrays (sampled tokens, entropy /
  freeze telemetry, recovery requests) and immediately starts their D2H
  copies (``jax.Array.copy_to_host_async``); the entry is materialized at
  step N+1, by which point the copy has overlapped the host's post-dispatch
  work (prefill chunk prep, event logging, the next tick's maintenance).
  Depth 0 degenerates to the synchronous path — push immediately followed
  by a blocking pop — so both modes share one code path and differ only in
  when the host waits.

* ``HostStaging`` — reused host-side staging buffers for the
  boundary-tick pull and the speculative thaw uploads.  On TPU these would be pinned host allocations
  (the DMA engine requirement for async H2D); here they model the reuse:
  one buffer per transfer role, reallocated only when shapes change, so
  steady-state ticks allocate nothing.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax


def _nbytes(x) -> int:
    try:
        return int(x.nbytes)
    except Exception:                      # scalars / python ints
        return 0


@dataclasses.dataclass
class TransferStats:
    """Counts every host<->device transfer an engine issues.

    *Blocking* transfers stall the host: a direct ``device_get`` /
    ``device_put`` whose data was not already in flight (boundary-tick pool
    pulls, un-prefetched thaw uploads, depth-0 ring pops).  *Async*
    transfers were issued ahead of use (ring fetches, speculative thaw
    staging) — the host may still wait on them at consume time, which a
    profiler capture shows as the ``repro:ring.wait`` span.  Byte counts
    are what crossed the bus: each array at the width it was sent,
    padding included.
    """
    blocking_d2h: int = 0
    blocking_h2d: int = 0
    async_d2h: int = 0
    async_h2d: int = 0
    d2h_bytes: int = 0
    h2d_bytes: int = 0
    blocked_s: float = 0.0      # host time inside blocking transfers
    steps: int = 0              # engine steps observed (begin/end bracket)
    blocked_steps: int = 0      # steps with >= 1 blocking transfer
    _step_open: bool = dataclasses.field(default=False, repr=False)
    _step_blocked: bool = dataclasses.field(default=False, repr=False)

    # ---- per-step bracketing ---------------------------------------- #
    def begin_step(self) -> None:
        self._step_open = True
        self._step_blocked = False

    def end_step(self) -> None:
        if not self._step_open:
            return
        self.steps += 1
        if self._step_blocked:
            self.blocked_steps += 1
        self._step_open = False

    def cancel_step(self) -> None:
        """Close the bracket without counting it (no jitted step ran —
        e.g. a drain-only or prefill-only engine call)."""
        self._step_open = False

    # ---- transfer notes --------------------------------------------- #
    def note_blocking(self, nbytes: int, d2h: bool, seconds: float = 0.0
                      ) -> None:
        if d2h:
            self.blocking_d2h += 1
            self.d2h_bytes += nbytes
        else:
            self.blocking_h2d += 1
            self.h2d_bytes += nbytes
        self.blocked_s += seconds
        if self._step_open:
            self._step_blocked = True

    def note_async(self, nbytes: int, d2h: bool) -> None:
        if d2h:
            self.async_d2h += 1
            self.d2h_bytes += nbytes
        else:
            self.async_h2d += 1
            self.h2d_bytes += nbytes

    # ---- derived metrics -------------------------------------------- #
    @property
    def host_blocked_fraction(self) -> float:
        """Share of engine steps that stalled on a blocking transfer."""
        return self.blocked_steps / self.steps if self.steps else 0.0

    def snapshot(self) -> Dict[str, Any]:
        return {
            "blocking_d2h": self.blocking_d2h,
            "blocking_h2d": self.blocking_h2d,
            "async_d2h": self.async_d2h,
            "async_h2d": self.async_h2d,
            "d2h_bytes": self.d2h_bytes,
            "h2d_bytes": self.h2d_bytes,
            "blocked_s": round(self.blocked_s, 4),
            "steps": self.steps,
            "blocked_steps": self.blocked_steps,
            "host_blocked_fraction": round(self.host_blocked_fraction, 4),
        }


class FetchRing:
    """Double-buffered async device->host fetch ring.

    ``push(meta, arrays)`` starts the D2H copy of every array and enqueues
    the entry; ``pop()`` materializes the oldest entry to numpy.  With
    ``depth >= 1`` the engine consumes entries one step after pushing them
    — the copy overlaps the intervening host work and device compute (and
    the pop is recorded as an *async* transfer).  With ``depth == 0`` the
    engine pops right after pushing (the synchronous baseline: the pop is
    recorded as *blocking*).

    The ring never reorders: entries drain FIFO, so host bookkeeping
    (token commits, rewinds, thaw requests, retirement) is applied in
    exactly the order the synchronous path applies it — which is what
    makes async-vs-sync token parity exact.
    """

    def __init__(self, stats: TransferStats, depth: int = 1,
                 endpoint: Optional[Any] = None):
        assert depth in (0, 1), "the pipeline is single- or double-buffered"
        self.stats = stats
        self.depth = depth
        # optional faults.Endpoint guarding the pop materialization (the
        # "ring" injection point).  must_succeed: a step's tokens/telemetry
        # either reach the host or the engine has nothing to commit.  The
        # engine watches this endpoint's breaker and drops ``depth`` to 0
        # (the synchronous baseline — token-identical by the FIFO-drain
        # design above) while it is tripped.
        self.endpoint = endpoint
        self._entries: List[Tuple[Dict[str, Any], Dict[str, Any]]] = []

    def __len__(self) -> int:
        return len(self._entries)

    def push(self, meta: Dict[str, Any], arrays: Dict[str, Any]) -> None:
        for a in arrays.values():
            start = getattr(a, "copy_to_host_async", None)
            if start is not None:
                start()
        self._entries.append((meta, arrays))

    def pop(self) -> Optional[Tuple[Dict[str, Any], Dict[str, Any]]]:
        """Materialize and return the oldest (meta, host arrays) entry."""
        if not self._entries:
            return None
        import numpy as np
        meta, arrays = self._entries.pop(0)
        nbytes = sum(_nbytes(v) for v in arrays.values())

        def _materialize():
            return {k: np.asarray(v) for k, v in arrays.items()}

        with jax.profiler.TraceAnnotation("repro:ring.wait", bytes=nbytes):
            t0 = time.perf_counter()
            if self.endpoint is not None:
                host = self.endpoint.call(_materialize)
            else:
                host = _materialize()
            dt = time.perf_counter() - t0
        if self.depth == 0:
            self.stats.note_blocking(nbytes, d2h=True, seconds=dt)
        else:
            self.stats.note_async(nbytes, d2h=True)
        return meta, host

    def drain(self):
        """Pop every pending entry (oldest first)."""
        while self._entries:
            yield self.pop()


class HostStaging:
    """Reused host staging buffers (the pinned-memory stand-in).

    ``buf(name, shape, dtype)`` returns a numpy buffer that persists across
    calls; it is reallocated only when the requested shape/dtype changes,
    so the steady-state boundary tick reuses the same allocation for its
    pull.
    """

    def __init__(self):
        self._bufs: Dict[str, Any] = {}

    def buf(self, name: str, shape, dtype):
        import numpy as np
        b = self._bufs.get(name)
        if b is None or b.shape != tuple(shape) or b.dtype != np.dtype(dtype):
            b = np.empty(shape, dtype)
            self._bufs[name] = b
        return b

    @property
    def nbytes(self) -> int:
        return sum(b.nbytes for b in self._bufs.values())
