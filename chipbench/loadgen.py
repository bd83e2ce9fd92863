"""The one generator of serving traffic: an open loop of arrivals with
prompt and output lengths, all from a traffic file's parameters and a seed.

Every seed gets the SAME lengths and the SAME gaps between arrivals
(stratified quantiles of the file's distributions) in the SAME cyclic order,
which the file's ``base_seed`` fixes; the run's seed decides where in that
cycle a stretch begins, and the prompts' tokens. A tail is made by how a
burst of short gaps falls on long prompts; with a rotation every seed offers
the same coincidences, from another starting point, so seeds do not differ
in the work they offer. What is left between runs is where each arrival
falls within the scheduler's step, which no schedule fixes: the time to the
first token is spread evenly over one step, and its 95th percentile over N
requests scatters as 1/sqrt(N) (records/serve_rot20, serve_rot35).

The stream has three stretches on one clock: ``warm`` (unmeasured, fills
the slots), ``window`` (requests DUE here are the measured ones) and
``tail`` (unmeasured filler so that the measured requests finish under the
same load they arrived in).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float            # on the stream's clock, 0 = start of ``warm``
    prompt: np.ndarray      # int32 tokens
    output_len: int
    measured: bool


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` whole lengths at evenly spaced quantiles of ``spec``'s
    distribution (``log_uniform`` between ``min`` and ``max``)."""
    if spec["dist"] != "log_uniform":
        raise ValueError(f"no length distribution {spec['dist']!r}")
    lo, hi = math.log(spec["min"]), math.log(spec["max"])
    return np.rint(np.exp(lo + (hi - lo) * _quantiles(n))).astype(np.int64)


def gaps(spec: Dict[str, Any], n: int, seconds: float) -> np.ndarray:
    """``n`` gaps between arrivals that sum to ``seconds``: quantiles of the
    exponential distribution for ``exponential_quantiles`` (the gaps of a
    Poisson process, each length once and none drawn), equal gaps for
    ``uniform``."""
    if spec["gaps"] == "exponential_quantiles":
        raw = -np.log1p(-_quantiles(n))
    elif spec["gaps"] == "uniform":
        raw = np.ones(n)
    else:
        raise ValueError(f"no gaps {spec['gaps']!r}")
    return raw * (seconds / raw.sum())


def _stretch(traffic, rng, which: int, start: float, seconds: float,
             vocab: int, measured: bool) -> List[Arrival]:
    n = int(round(traffic["arrivals"]["rate_per_s"] * seconds))
    if n == 0:
        return []
    order = np.random.default_rng([traffic["base_seed"], which])
    shift = int(rng.integers(n))

    def cycle(values):
        return np.roll(order.permutation(values), shift)

    prompt_len = cycle(lengths(traffic["prompt_len"], n))
    output_len = cycle(lengths(traffic["output_len"], n))
    due = start + np.cumsum(cycle(gaps(traffic["arrivals"], n, seconds)))
    # the last gap ends on the stretch's edge: keep every arrival inside it
    due = np.minimum(due, start + seconds - 1e-9)
    return [Arrival(float(d), rng.integers(0, vocab, p).astype(np.int32),
                    int(o), measured)
            for d, p, o in zip(due, prompt_len, output_len)]


def stream(traffic: Dict[str, Any], seed: int, seconds: float,
           vocab: int) -> List[Arrival]:
    """All arrivals of a run, in due order."""
    rng = np.random.default_rng(seed)
    warm, tail = traffic["warm_seconds"], traffic["tail_seconds"]
    return (_stretch(traffic, rng, 0, 0.0, warm, vocab, False)
            + _stretch(traffic, rng, 1, warm, seconds, vocab, True)
            + _stretch(traffic, rng, 2, warm + seconds, tail, vocab, False))
