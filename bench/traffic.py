"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a run's seed into the requests a run sends.

A mix file holds parameters only:

  serving       engine settings for the cell (lanes, pages, prefill chunk,
                max_seq, freeze on or off)
  loop          "closed": ``clients`` clients, each sending its next request
                when the previous one finishes (request i belongs to client
                i mod clients); "open": arrivals at
                ``rate_per_s`` (Poisson), sent whether or not earlier ones
                finished
  prompt/output length distributions: {"dist": "fixed", "value": n},
                {"dist": "loguniform", "lo": a, "hi": b} or
                {"dist": "lognormal", "median": m, "sigma": s,
                 "clip": [a, b]}; "round": "pow2" rounds a length up to a
                power of two
  greedy_share  share of requests decoded greedily; the rest sample at
                ``temperature``
  sizes_seed    the multiset of lengths (and, open loop, of gaps between
                arrivals) comes from this fixed seed, so every run seed
                sends the same work; the run seed only orders it and draws
                the prompt tokens

Seeds may exceed 32 bits; they go through ``numpy.random.SeedSequence``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request as the client will send it."""
    index: int
    prompt: np.ndarray          # int32 token ids
    n_tokens: int
    greedy: bool
    due_s: Optional[float]      # open loop: seconds after the stream starts


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), *salt]))


def round_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 1).bit_length()


def draw_lengths(spec: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        out = np.full(n, float(spec["value"]))
    elif dist == "loguniform":
        out = np.exp(rng.uniform(math.log(spec["lo"]), math.log(spec["hi"]),
                                 n))
    elif dist == "lognormal":
        out = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    if "clip" in spec:
        out = np.clip(out, *spec["clip"])
    out = np.maximum(np.round(out).astype(np.int64), 1)
    if spec.get("round") == "pow2":
        out = np.array([round_pow2(x) for x in out], np.int64)
    return out


def n_requests(mix: Dict, seconds: float) -> int:
    """How many requests a run plans: every closed-loop client's first
    request plus ``followups`` each; open loop, the arrivals of the warm
    period and the window (the multiset is drawn for this count)."""
    if mix["loop"] == "closed":
        return mix["clients"] * (1 + mix.get("followups", 0))
    return int(math.ceil(mix["rate_per_s"] * (mix["warm_s"] + seconds)))


def plan(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Planned]:
    """The run's requests, in sending order (open loop: by due time)."""
    n = n_requests(mix, seconds)
    fixed = rng_for(mix["sizes_seed"])
    prompts = draw_lengths(mix["prompt"], fixed, n)
    outputs = draw_lengths(mix["output"], fixed, n)
    n_greedy = int(round(mix["greedy_share"] * n))
    greedy = np.arange(n) < n_greedy
    run = rng_for(seed)
    order = run.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    greedy = greedy[run.permutation(n)]
    due = [None] * n
    if mix["loop"] == "open":
        gaps = fixed.exponential(1.0 / mix["rate_per_s"], n)
        gaps = gaps[run.permutation(n)]
        due = list(np.cumsum(gaps) - gaps[0])
    toks = rng_for(seed, 1)
    out = []
    for i in range(n):
        out.append(Planned(
            index=i,
            prompt=toks.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            n_tokens=int(outputs[i]), greedy=bool(greedy[i]),
            due_s=None if due[i] is None else float(due[i])))
    return out
