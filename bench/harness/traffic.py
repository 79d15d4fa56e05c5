"""The one traffic generator: turns a mix's data file into a schedule of
requests.

A mix is a JSON file under ``bench/traffic``.  Its keys:

``loop``            ``"closed"`` or ``"open"``.
``clients``         closed loop: requests kept in flight, no think time.
``rate_rps``        open loop: offered Poisson rate, fixed in the mix.
``prompt_tokens``   optional length distribution of prompts, and
``output_tokens``   of outputs: ``{"median", "sigma", "min", "max"}`` of a
                    lognormal, rounded to whole tokens.

Lengths are the distribution's quantiles at ``(k + 0.5) / n`` and gaps the
exponential's, put in an order drawn once from a constant: every seed
replays the same schedule, and ``--seed`` draws what the requests hold (the
tokens, the offsets, the weights), which the configuration makes.  Runs with
different seeds then do the same work; when the seed also set the order, the
tail of an open-loop cell moved with which requests happened to overlap
(``tpot_p95_ms`` of ``disagg-chat`` read 34.6 to 41.7 ms over six seeds, and
within 0.2 to 5% between two runs of one seed).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

#: closed loop: requests drawn before the schedule repeats itself
POOL = 4096
#: draws the order of sizes and gaps, the same for every seed
ORDER_SEED = 0x7AFF1C

@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    #: seconds after the window opens at which the request is due (open loop)
    due_s: Optional[float] = None
    prompt_tokens: Optional[int] = None
    output_tokens: Optional[int] = None


@dataclasses.dataclass
class Schedule:
    loop: str
    clients: int
    rate_rps: Optional[float]
    #: open loop: every request due in the window; closed loop: a pool that
    #: ``spec(i)`` cycles through
    requests: List[RequestSpec]

    def spec(self, i: int) -> RequestSpec:
        if self.loop == "open":
            return self.requests[i]
        base = self.requests[i % len(self.requests)]
        return dataclasses.replace(base, index=i)

    def lengths(self, key: str) -> List[int]:
        """The distinct values of ``prompt_tokens`` or ``output_tokens``."""
        return sorted({getattr(r, key) for r in self.requests
                       if getattr(r, key) is not None})


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths: lognormal quantiles, clipped."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(rate_rps: float, n: int) -> np.ndarray:
    return -np.log1p(-_quantiles(n)) / rate_rps


def make_schedule(mix: dict, seconds: float) -> Schedule:
    loop = mix["loop"]
    if loop not in ("open", "closed"):
        raise ValueError(f"loop must be 'open' or 'closed', got {loop!r}")
    rng = np.random.default_rng(ORDER_SEED)
    if loop == "open":
        rate = float(mix["rate_rps"])
        n = max(1, int(math.floor(rate * seconds)))
        gaps = rng.permutation(exponential_gaps(rate, n))
        due = np.cumsum(gaps) - gaps[0]        # the first request opens the window
        # the quantile gaps sum to about n / rate: keep what falls inside
        n = int(np.searchsorted(due, seconds, side="left"))
        due = due[:n]
    else:
        n = POOL
        due = None
    lens = {}
    for key in ("prompt_tokens", "output_tokens"):
        if key in mix:
            lens[key] = rng.permutation(lognormal_lengths(mix[key], n))
    reqs = [
        RequestSpec(
            index=i,
            due_s=None if due is None else float(due[i]),
            prompt_tokens=int(lens["prompt_tokens"][i]) if "prompt_tokens" in lens else None,
            output_tokens=int(lens["output_tokens"][i]) if "output_tokens" in lens else None,
        )
        for i in range(n)
    ]
    return Schedule(loop, int(mix.get("clients", 0)),
                    float(mix["rate_rps"]) if loop == "open" else None, reqs)
