"""Open-loop request traffic from a mix's parameter file and a seed.

A mix (``bench/traffic/<name>.json``) gives an arrival process and two
length distributions:

    {"arrival": {"process": "poisson", "rate_per_s": 2.0},
     "prompt_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 32, "max": 512},
     "output_tokens": {...}, "lead_in_s": 30}

``process`` is ``poisson`` (exponential gaps) or ``gamma`` (gaps with a
squared coefficient of variation ``cv2``: ``cv2`` 1 is Poisson, larger is
burstier).  ``dist`` is ``lognormal`` (``median``, ``sigma``) or
``fixed`` (``value``); draws are rounded and clipped to ``[min, max]``.

A run is cut into stretches (the lead-in, the measured window, the drain
after it), and every seed offers the same work in each stretch, in
another arrangement:

* a stretch of ``s`` seconds holds ``round(rate * s)`` arrivals, placed
  by the process conditioned on that count: ``n + 1`` gaps drawn from the
  seed and scaled to span the stretch.  For Poisson this is exactly the
  law of a Poisson process given its count (uniform arrival times), with
  its bursts and lulls; only the count is fixed;
* the stretch's ``n`` prompt lengths and ``n`` answer lengths are the
  ``n`` stratified quantiles of their distributions (down to the
  ``0.5/n`` and up to the ``1 - 0.5/n`` quantile, so a stretch of some
  tens reaches the tails and the clip), each in its own order drawn from
  the seed.

Seeds therefore differ in when requests come and which lengths come
together, and not in how much work a stretch offers.  The quantiles are
read from a large sample drawn with a fixed seed, so any distribution
numpy draws from can be added without new quantile code.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import numpy as np

_QUANTILE_SAMPLE = 200_000


@dataclass
class Request:
    rid: int
    due_s: float              # offset from the start of the lead-in
    prompt: np.ndarray        # int32 token ids
    max_new_tokens: int


def _draws(spec: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    d = spec["dist"]
    if d == "lognormal":
        return np.exp(math.log(spec["median"]) + spec["sigma"]
                      * rng.standard_normal(n))
    if d == "fixed":
        return np.full((n,), float(spec["value"]))
    raise ValueError(f"unknown length distribution {d!r}")


def _gap_draws(spec: Dict, rng: np.random.Generator, n: int) -> np.ndarray:
    rate = float(spec["rate_per_s"])
    p = spec["process"]
    if p == "poisson":
        return rng.exponential(1.0 / rate, n)
    if p == "gamma":
        shape = 1.0 / float(spec["cv2"])
        return rng.gamma(shape, 1.0 / (rate * shape), n)
    raise ValueError(f"unknown arrival process {p!r}")


def stratified(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles (midpoints of ``n`` equal strata)
    of a length distribution, rounded and clipped."""
    sample = _draws(spec, np.random.default_rng(0), _QUANTILE_SAMPLE)
    v = np.rint(np.quantile(sample, (np.arange(n) + 0.5) / n))
    return np.clip(v, spec["min"], spec["max"]).astype(np.int64)


def stretches(mix: Dict, seconds: float, drain_s: float) -> List[float]:
    """A run's stretches: the lead-in, the window and the longest drain."""
    return [float(mix["lead_in_s"]), float(seconds), float(drain_s)]


def generate(mix: Dict, seed: int, spans: Sequence[float],
             vocab_size: int) -> List[Request]:
    """Requests due over consecutive stretches of ``spans`` seconds, in
    order of due time."""
    rate = float(mix["arrival"]["rate_per_s"])
    rng = np.random.default_rng(int(seed) % 2**64)
    out: List[Request] = []
    start = 0.0
    for span in spans:
        n = int(round(rate * span))
        if n:
            plen = rng.permutation(stratified(mix["prompt_tokens"], n))
            olen = rng.permutation(stratified(mix["output_tokens"], n))
            g = np.cumsum(_gap_draws(mix["arrival"], rng, n + 1))
            due = start + span * g[:n] / g[n]
            for t, p, o in zip(due, plen, olen):
                prompt = rng.integers(0, vocab_size, size=int(p),
                                      dtype=np.int32)
                out.append(Request(len(out), float(t), prompt, int(o)))
        start += span
    return out
