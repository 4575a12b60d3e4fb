"""Client-side records of a run and the arithmetic of the end-to-end
metrics over the measured window ``[t0, t1]`` (host-clock seconds).

Every rate and tail covers the whole window: a request still waiting for
its first token at ``t1`` counts at its elapsed time, and a gap between
tokens still open at ``t1`` counts at its elapsed length.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Record:
    """What the client saw of one request."""
    index: int
    prompt: np.ndarray
    n_tokens: int
    greedy: bool
    uid: Optional[int] = None            # the server's request id
    due: Optional[float] = None          # when the request was due to be sent
    sent: Optional[float] = None         # when the client sent it
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    rewinds: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)            # (time, tokens taken back)
    done: Optional[float] = None
    status: Optional[str] = None

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


def decode_tok_s(recs: Sequence[Record], t0: float, t1: float) -> float:
    """Tokens streamed to clients in the window, net of rewinds, over the
    window's length."""
    n = 0
    for r in recs:
        n += sum(1 for t in r.times if t0 <= t <= t1)
        n -= sum(k for t, k in r.rewinds if t0 <= t <= t1)
    return n / (t1 - t0)


def itl_gaps(recs: Sequence[Record], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive token events of one request that ends
    in the window, and each gap still open at ``t1`` at its elapsed
    length (seconds)."""
    gaps: List[float] = []
    for r in recs:
        ts = r.times
        for a, b in zip(ts, ts[1:]):
            if t0 <= b <= t1:
                gaps.append(b - a)
        if ts and (r.done is None or r.done > t1):
            last = max((t for t in ts if t <= t1), default=None)
            if last is not None:
                gaps.append(t1 - last)
    return gaps


def ttfts(recs: Sequence[Record], t0: float, t1: float) -> List[float]:
    """Time to first token of every request due in the window, from when
    it was due; one without a first token by ``t1`` counts at ``t1``."""
    out = []
    for r in recs:
        if r.due is None or not t0 <= r.due <= t1:
            continue
        first = r.first
        out.append((first if first is not None and first <= t1 else t1)
                   - r.due)
    return out


def attempted_failed(recs: Sequence[Record], t0: float, t1: float,
                     ok: Sequence[str]) -> Tuple[int, int]:
    """Requests the window attempted: open loop, those due in it; closed
    loop, those in flight at some time in it.  Failed: those of them that
    ended in a status not in ``ok``."""
    att = [r for r in recs
           if (t0 <= r.due <= t1 if r.due is not None else
               r.sent is not None and r.sent <= t1
               and (r.done is None or r.done >= t0))]
    failed = sum(1 for r in att
                 if r.status is not None and r.status not in ok)
    return len(att), failed
