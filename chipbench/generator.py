"""The one traffic generator. A mix is a data file (`traffic/<mix>.json`);
this module turns it and a seed into a schedule of requests.

The schedule is DETERMINISTIC, not a random draw: lengths and inter-arrival
gaps are the quantiles of the mix's distributions (`quantile_exponential`
gaps have the exponential distribution of a Poisson process, but they are
its quantiles, not samples), shuffled once into one fixed cycle. --seed
only chooses where the cycle is entered, and every token id. The window
[0, seconds) of an open-loop mix holds exactly one cycle, so every seed's
window offers the same requests with the same neighbours, rotated: a tail
over some fifty requests is then the tail of ONE sample path, which is what
lets a bound of a few percent hold across the driver's seeds, and what it
costs is said in PERF.md (section 2, "Steadiness by construction").

Before the window a ramp (times < 0) fills the batch and is not counted;
after it a tail keeps the load on while the counted requests finish. A
backlog mix has everything due at the ramp's start, in cycles of `block`
requests. Nothing here imports JAX.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class ScheduledRequest:
    rid: str
    due_s: float               # relative to the window's start
    prompt_ids: List[int]
    max_tokens: int
    counted: bool              # due inside [0, seconds)


def quantile_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """n lengths at the mid-quantiles (i + 0.5) / n of the distribution,
    clipped to [min, max], as integers, in increasing order."""
    if n <= 0:
        return np.zeros((0,), np.int64)
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(x)) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def quantile_gaps(arrivals: Dict[str, Any], n: int,
                  span_s: float) -> np.ndarray:
    """n inter-arrival gaps scaled so that they sum to span_s:
    `quantile_exponential`, the mid-quantiles of the exponential
    distribution (the gaps of a Poisson process, not drawn from it), or
    `uniform`, all equal."""
    if n <= 0:
        return np.zeros((0,), np.float64)
    u = (np.arange(n) + 0.5) / n
    proc = arrivals["process"]
    if proc == "quantile_exponential":
        gaps = -np.log1p(-u)
    elif proc == "uniform":
        gaps = np.ones((n,))
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return gaps * (span_s / gaps.sum())


def _base_cycle(mix: Dict[str, Any], n: int, span_s: float):
    """The one cycle of n requests: lengths at the mix's quantiles, outputs
    paired with prompts at random, shuffled once; n gaps that sum to
    span_s, shuffled once. The shuffle is the same for every run of every
    mix. Returns (prompt_len[n], output_len[n], gaps[n])."""
    rng = np.random.default_rng([0, 0xC1C1E])
    output = rng.permutation(quantile_lengths(mix["output_len"], n))
    order = rng.permutation(n)
    prompt = quantile_lengths(mix["prompt_len"], n)[order]
    output = output[order]
    gaps = rng.permutation(quantile_gaps(mix["arrivals"], n, span_s))
    return prompt, output, gaps


def make_schedule(mix: Dict[str, Any], seed: int, seconds: float,
                  vocab: int) -> List[ScheduledRequest]:
    """All requests of one run, sorted by due time.

    The cycle (which length follows which, after which gap) is fixed, not
    drawn from --seed: --seed chooses where the cycle is entered and every
    token id. So every seed's window holds the same requests with the same
    neighbours, rotated, and tails that hang on coincidences (a long prompt
    just ahead of others) repeat from seed to seed instead of being
    redrawn."""
    seed = int(seed)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 0x5EED])
    arrivals = mix["arrivals"]
    ramp, grace = float(mix.get("ramp_s", 0)), float(mix.get("grace_s", 0))
    backlog = arrivals["process"] == "backlog"
    slots: List[tuple] = []        # (position in the cycle, due, counted)
    if backlog:
        n_cycle = int(mix.get("block", 16))
        total = int(math.ceil(arrivals["max_rate_per_s"]
                              * (ramp + seconds + grace)))
        prompt, output, gaps = _base_cycle(
            dict(mix, arrivals={"process": "uniform"}), n_cycle, 1.0)
        offset = seed % n_cycle
        slots = [(j, -ramp, True) for j in range(total)]
    else:
        n_cycle = max(1, int(round(float(arrivals["rate_per_s"]) * seconds)))
        prompt, output, gaps = _base_cycle(mix, n_cycle, float(seconds))
        offset = seed % n_cycle
        # forward from the window's start through the tail; exactly one
        # cycle is counted ...
        t, j = 0.0, 0
        while t < seconds + grace:
            slots.append((j, t, j < n_cycle))
            t += float(gaps[(offset + j) % n_cycle])
            j += 1
        # ... and backwards through the ramp
        t, j = -float(gaps[(offset - 1) % n_cycle]), -1
        while t >= -ramp:
            slots.append((j, t, False))
            j -= 1
            t -= float(gaps[(offset + j) % n_cycle])
    reqs = []
    for j, due, cnt in sorted(slots, key=lambda x: (x[1], x[0])):
        i = (offset + j) % n_cycle
        toks = rng.integers(0, vocab, int(prompt[i])).tolist()
        tag = "w" if cnt and not backlog else ("b" if backlog else
                                               ("r" if j < 0 else "t"))
        reqs.append(ScheduledRequest(
            rid=f"{tag}{j}", due_s=float(due), prompt_ids=toks,
            max_tokens=int(output[i]), counted=bool(cnt)))
    return reqs


def make_token_batches(job: Dict[str, Any], seed: int, n: int,
                       vocab: int) -> np.ndarray:
    """n training batches [n, batch, seq] of token ids from the seed."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 0xBA7C4])
    return rng.integers(0, vocab, (n, int(job["batch"]), int(job["seq"])),
                        dtype=np.int32)
