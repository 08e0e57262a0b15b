"""The one traffic generator. A mix is a data file of parameters
(perfbench/traffic/<name>.json); a cell adds the numbers that belong
to configuration x traffic (perfbench/cells/<workload>.json).

What the traffic file and the cell fix, every seed shares: the set of
(prompt, output) sizes, the set of gaps between arrivals, the standing
population. `--seed` decides the order of both sets and the token ids.
So two seeds offer the same work in another order, and the set of
shapes the server sees is the same in every run. A backlog is the
exception: its queue outlasts the window, so the order of the queue
decides which requests are served at all, and the order is the work.
Every seed gets the same queue, one random order drawn with the
sizes; the seed draws the token ids, the weights and the order in
which the standing population is seated.

Vocabulary of a traffic file:

  arrival   {"kind": "poisson", "share_of_knee": s}  exponential gaps: one
                                                     stratified set, mean 1/rate,
                                                     in seeded order (a Poisson
                                                     process held to its count)
            {"kind": "backlog"}                      all due at time 0, in one
                                                     random order for every seed
            {"kind": "bursts", "share_of_knee": s,
             "on_s": a, "off_s": b, "factor": f}     on at f x mean rate,
                                                     off at what keeps the mean
  *_tokens  {"dist": "uniform" | "loguniform", "low": l, "high": h}
            {"dist": "choice", "values": [...]}
  standing  {"population": "max_batch" | "rate_x_lifetime"}
            requests seated during set-up
  shared_prefix_tokens   leading tokens every prompt has in common
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

# Pairs the set of prompt sizes with the set of output sizes, the same
# way for every mix and every seed.
SIZES_SEED = 1


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float  # offset from the window's opening; < 0: standing
    prompt_tokens: int
    output_tokens: int  # tokens the server is asked for
    standing: bool


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec["dist"]
    if dist == "choice":
        values = np.asarray(spec["values"], np.int64)
        # Each value equally often, as far as n allows.
        reps = -(-n // len(values))
        return rng.permutation(np.tile(values, reps))[:n]
    low, high = spec["low"], spec["high"]
    # Stratified: the i-th of n equal shares of the distribution.
    u = (rng.permutation(n) + 0.5) / n
    if dist == "uniform":
        x = low + u * (high - low)
    elif dist == "loguniform":
        x = np.exp(math.log(low) + u * (math.log(high) - math.log(low)))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), low, high).astype(np.int64)


def exponential_gaps(n: int) -> np.ndarray:
    """n gaps of a unit-rate Poisson process, stratified: the i-th gap
    is the (i+0.5)/n quantile of the exponential distribution, scaled
    so that the mean is exactly 1. The set is the same for every seed,
    and so is its sum: in whatever order the gaps come, the n-th
    arrival falls at time n."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps / gaps.mean()


def warp(unit_times: np.ndarray, arrival: dict, rate: float) -> np.ndarray:
    """Arrival times of a process whose rate follows `arrival`, from
    the times of a unit-rate process: the inverse of the cumulative
    rate. poisson is the straight line t / rate."""
    kind = arrival["kind"]
    if kind == "poisson":
        return unit_times / rate
    if kind != "bursts":
        raise ValueError(f"unknown arrival kind {kind!r}")
    on, off, f = arrival["on_s"], arrival["off_s"], arrival["factor"]
    low = (on + off - on * f) / off  # share of the mean rate when off
    if low < 0:
        raise ValueError("bursts: factor * on_s exceeds the period")
    per_period = rate * (on + off)  # arrivals in one period
    k, rest = np.divmod(unit_times, per_period)
    in_on = rest <= rate * f * on
    t_on = rest / (rate * f)
    t_off = on + (rest - rate * f * on) / np.where(low > 0, rate * low, 1.0)
    return k * (on + off) + np.where(in_on, t_on, t_off)


def offered_rate(traffic: dict, cell: dict) -> float | None:
    arrival = traffic["arrival"]
    if arrival["kind"] == "backlog":
        return None
    return arrival["share_of_knee"] * cell["knee_per_s"]


def generate(
    traffic: dict, cell: dict, *, seconds: float, max_batch: int, seed: int
) -> list[Request]:
    """Every request of one run: the standing population first (due
    < 0, in the order they are seated), then arrivals by due time."""
    fixed = np.random.default_rng(SIZES_SEED)
    order = np.random.default_rng(seed)
    arrival = traffic["arrival"]
    rate = offered_rate(traffic, cell)
    if rate is None:
        n = round(cell["backlog_per_s"] * seconds)
        due = np.zeros(n)
    else:
        # As many arrivals as the window takes at this rate: the last
        # is due as the window closes, whatever the order of the gaps.
        n = max(1, math.floor(rate * seconds))
        gaps = order.permutation(exponential_gaps(n))
        due = warp(np.cumsum(gaps), arrival, rate)
    prompts = draw_lengths(traffic["prompt_tokens"], n, fixed)
    outputs = draw_lengths(traffic["output_tokens"], n, fixed)
    # Only a prefix of a backlog is served, so its order is the work
    # (seeds in seeded order differed by 2.9% in tokens/s where one
    # seed repeats to 0.3%: PERF.md, PR 31): one order for every seed.
    shuffle = (fixed if rate is None else order).permutation(n)
    requests = [
        Request(float(due[i]), int(prompts[j]), int(outputs[j]), False)
        for i, j in enumerate(shuffle)
    ]

    standing = traffic["standing"]
    if standing["population"] == "max_batch":
        k = max_batch
    elif standing["population"] == "rate_x_lifetime":
        k = min(max_batch, round(rate * cell["lifetime_s"]))
    else:
        raise ValueError(
            f"unknown standing population {standing['population']!r}"
        )
    s_prompts = draw_lengths(traffic["prompt_tokens"], k, fixed)
    s_outputs = draw_lengths(traffic["output_tokens"], k, fixed)
    seated = []
    for i in range(k):
        # What is left of a request met at a uniformly random point of
        # its stream, stratified over the population: completions are
        # staggered from the first second.
        left = max(1, math.ceil(s_outputs[i] * (i + 0.5) / k))
        seated.append(Request(-1.0, int(s_prompts[i]), left, True))
    seated = [seated[i] for i in order.permutation(k)]
    return seated + requests


def token_ids(
    requests: list[Request], vocab: int, shared_prefix: int, seed: int
) -> list[np.ndarray]:
    """Prompt ids [1, T] for each request, from the seed; the first
    `shared_prefix` ids are one seeded prefix common to all."""
    rng = np.random.default_rng([seed, 1])
    prefix = rng.integers(1, vocab, shared_prefix)
    out = []
    for r in requests:
        ids = rng.integers(1, vocab, r.prompt_tokens)
        m = min(shared_prefix, r.prompt_tokens)
        ids[:m] = prefix[:m]
        out.append(ids[None, :].astype(np.int32))
    return out
