"""The one traffic generator: reads a mix file (``bench/traffic/<mix>.json``)
and draws requests from a seed.

Every seed gets the same multiset of sizes and of arrival gaps, only in
another order: lengths and gaps are the quantiles of the mix's
distributions at stratified levels, and the seed permutes them inside
blocks of ``strata`` requests.  So any window of a run holds nearly the
same mix, and runs of different seeds differ in order and in token ids,
not in the work they carry.

A mix file holds:

* ``arrival``: ``{"kind": "closed", "clients": n}`` (each client sends its
  next request when the last one finished) or ``{"kind": "poisson",
  "rate": r}`` (open loop, requests per second), with ``preroll_s``, the
  seconds arrivals run before the measured window;
* ``prompt``/``output``: token counts, ``{"dist": "fixed", "value"}`` or
  ``{"dist": "lognormal", "median", "sigma", "min", "max"}``, with an
  optional ``round_up`` multiple for prompts;
* ``temperature``: every request samples at it;
* ``strata``: the block size of the stratification;
* ``engine``: ``max_slots`` and ``kv_page`` of the serving engine.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    index: int
    prompt: np.ndarray  # int32 token ids
    max_new: int
    greedy: bool
    gap_s: float = 0.0  # open loop: seconds after the previous arrival
    # filled in while serving
    rid: int = -1  # the engine's request id
    due: float = 0.0
    submitted: float = 0.0
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    # greedy requests: the program's logits each token was chosen from,
    # as (device array, row) pairs
    logits: list = field(default_factory=list)
    finished: float = 0.0
    status: str = ""


def _levels(n: int, strata: int, rng) -> np.ndarray:
    """n stratified levels in (0, 1): block b holds (i + 0.5) / strata for
    every i, in a seeded order."""
    out = []
    base = (np.arange(strata) + 0.5) / strata
    while len(out) < n:
        out.extend(rng.permutation(base))
    return np.asarray(out[:n])


def length_range(spec):
    """(least, most) tokens a length distribution can give."""
    if spec["dist"] == "fixed":
        return int(spec["value"]), int(spec["value"])
    r = spec.get("round_up", 1)
    return int(math.ceil(spec["min"] / r) * r), int(spec["max"])


def _lengths(spec, levels) -> np.ndarray:
    if spec["dist"] == "fixed":
        return np.full(len(levels), int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.asarray([NormalDist().inv_cdf(float(u)) for u in levels])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"])
    r = spec.get("round_up", 1)
    x = np.minimum(np.ceil(x / r) * r, spec["max"])
    return x.astype(np.int64)


def generate(mix, seed: int, vocab_size: int, n: int, *,
             greedy: bool = False, stream: int = 0) -> List[Request]:
    """The first ``n`` requests of the mix under ``seed``: the timed
    traffic (``stream`` 0), or another stream of the same sizes, such as
    the greedy requests the correctness check serves after the window."""
    rng = np.random.default_rng([int(seed), 0x5EED, int(stream)])
    strata = int(mix.get("strata", 16))
    plen = _lengths(mix["prompt"], _levels(n, strata, rng))
    olen = _lengths(mix["output"], _levels(n, strata, rng))
    arr = mix["arrival"]
    gaps = np.zeros(n)
    if arr["kind"] == "poisson":
        u = _levels(n, strata, rng)
        gaps = -np.log1p(-u) / float(arr["rate"])
    elif arr["kind"] != "closed":
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    return [Request(index=i,
                    prompt=rng.integers(0, vocab_size, int(plen[i]),
                                        dtype=np.int32),
                    max_new=int(olen[i]), greedy=greedy,
                    gap_s=float(gaps[i]))
            for i in range(n)]


def possible_prompt_lengths(mix) -> List[int]:
    """Every prompt length the mix can send."""
    lo, hi = length_range(mix["prompt"])
    return list(range(lo, hi + 1, mix["prompt"].get("round_up", 1)))
