"""The one generator of traffic: a mix is a data file of parameters.

Every seed gets the SAME schedule of sizes and arrival gaps (drawn once
from the mix's own ``plan_seed``) and its own token ids (and weights):
so two seeds offer the same work, and a difference between two runs is
the system's, not the draw's.  A Poisson schedule is stretched to hold
exactly the arrivals its rate states (``request_plan``).  (Reordering the schedule by the seed
was tried and moved the chat cell's tail by a sixth: PERF.md §7.)  The
arithmetic — exponential gaps, a burst state, inclusive length ranges —
is that of the program's ``serving/loadgen.py``, copied here so that a
later change to the program cannot move the yardstick."""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def seeded_rng(*words: int) -> np.random.Generator:
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFFFFFF
                                  for w in words])


def draw_lengths(spec: Dict, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """``n`` whole numbers in ``[min, max]``: ``uniform``,
    ``log_uniform`` or ``lognormal`` (heavy tail, clipped)."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if not 1 <= lo <= hi:
        raise ValueError(f"bad length range {spec!r}")
    dist = spec.get("dist", "uniform")
    if dist == "uniform":
        return rng.integers(lo, hi + 1, n)
    if dist == "log_uniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), n))
        return np.clip(x.astype(np.int64), lo, hi)
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
        return np.clip(x.astype(np.int64), lo, hi)
    raise ValueError(f"unknown length distribution {dist!r}")


def arrival_gaps(arrivals: Dict, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """Seconds between consecutive arrivals: exponential at
    ``rate_rps``, each gap drawn at ``burst_rate_rps`` with probability
    ``burst_fraction``.  A ``backlog`` has no gaps: all is due at 0."""
    if arrivals["process"] == "backlog":
        return np.zeros(n)
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals!r}")
    rate = np.full(n, float(arrivals["rate_rps"]))
    frac = float(arrivals.get("burst_fraction", 0.0))
    if frac > 0.0:
        rate = np.where(rng.random(n) < frac,
                        float(arrivals["burst_rate_rps"]), rate)
    return rng.exponential(1.0, n) / rate


def expected_arrivals(arrivals: Dict, seconds: float) -> int:
    """How many requests a Poisson mix offers in ``seconds``: the window
    over the mean gap, to the nearest whole number."""
    frac = float(arrivals.get("burst_fraction", 0.0))
    mean_gap = (1.0 - frac) / float(arrivals["rate_rps"])
    if frac > 0.0:
        mean_gap += frac / float(arrivals["burst_rate_rps"])
    return int(round(seconds / mean_gap))


def request_plan(traffic: Dict, seed: int, seconds: float, vocab: int
                 ) -> List[Dict]:
    """The requests of one run, in arrival order: ``{"at", "prompt",
    "max_new"}``.  ``at`` is the due time from the window's start.

    A Poisson schedule is stretched so that the window holds exactly
    the number of arrivals its rate states (the first that many; the
    next one falls on the window's end): a Poisson process conditioned
    on its count, since exponential gaps over their sum are the
    spacings of uniform order statistics.  ONE schedule serves every
    run, and one unstretched draw is off by its own chance, some tenth
    of the count (PR 29 found 125 arrivals in 30 s at a stated 3.6 a
    second, and 116 at a stated 4.5: PERF.md section 6)."""
    arrivals = traffic["arrivals"]
    if arrivals["process"] == "backlog":
        n = int(traffic["plan_requests"])
    else:
        n = int(math.ceil(arrivals["rate_rps"] * seconds * 1.25)) + 16
    shape = seeded_rng(traffic["plan_seed"], n)
    p_len = draw_lengths(traffic["prompt_tokens"], n, shape)
    o_len = draw_lengths(traffic["output_tokens"], n, shape)
    gap = arrival_gaps(arrivals, n, shape)
    at = np.cumsum(gap)
    if arrivals["process"] == "poisson":
        k = expected_arrivals(arrivals, seconds)
        at = at * (seconds / at[k])
        at[k:] = np.maximum(at[k:], seconds)    # rounding lets none in
    ids = seeded_rng(seed, 2)
    return [{"at": float(at[i]),
             "prompt": ids.integers(0, vocab, int(p_len[i]),
                                    dtype=np.int32),
             "max_new": int(o_len[i])}
            for i in range(n)]


class PackedDocuments:
    """A map-style dataset of packed training rows: row ``i`` is
    documents of heavy-tailed length, drawn from ``(seed, i)`` and laid
    end to end until ``seq_len + 1`` tokens are full (the last one is
    cut, as a packer does); returns ``(ids, labels)`` shifted by one.
    Every row differs, and any row is made on demand, so the stream has
    no end and holds nothing."""

    def __init__(self, traffic: Dict, seed: int, vocab: int):
        self.seq_len = int(traffic["seq_len"])
        self.doc = traffic["document_tokens"]
        self.rows = int(traffic["rows"])
        self.seed = int(seed)
        self.vocab = int(vocab)

    def __len__(self) -> int:
        return self.rows

    def __getitem__(self, i: int):
        rng = seeded_rng(self.seed, 3, i)
        need = self.seq_len + 1
        parts, have = [], 0
        while have < need:
            n = int(draw_lengths(self.doc, 1, rng)[0])
            parts.append(rng.integers(0, self.vocab, n, dtype=np.int32))
            have += n
        row = np.concatenate(parts)[:need]
        return row[:-1], row[1:]
