"""The arithmetic from token stamps to end-to-end metrics.

Pure Python over numbers the driver loop recorded: no JAX, no program
code. Every stamp is the benchmark's own `time.perf_counter()` taken in
the server's `on_token` callback, i.e. when a streaming client would
see the token. Only stamps inside the measured window are passed in.
"""

from __future__ import annotations

import math

# A request's time per output token is taken only where its stream has
# at least this many stamps inside the window: seven gaps, over a
# second of decoding, so that the host clock's half millisecond is
# small beside what is timed.
TPOT_MIN_TOKENS = 8
# tokens_per_s_slice_p50 cuts the window into slices of about this
# length whose edges are ends of dispatches.
SLICE_S = 5.0


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default), on a plain list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    k = (len(xs) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tpot_per_request(stamps_by_request: dict) -> list[float]:
    """Seconds per output token of each request with at least
    TPOT_MIN_TOKENS stamps: (last - first) / (tokens - 1) over the
    part of its stream that was passed in."""
    out = []
    for stamps in stamps_by_request.values():
        if len(stamps) >= TPOT_MIN_TOKENS:
            out.append((stamps[-1] - stamps[0]) / (len(stamps) - 1))
    return out


def inter_token_gaps(stamps_by_request: dict) -> list[float]:
    """Gaps between consecutive stamps of one request, pooled over
    requests."""
    gaps = []
    for stamps in stamps_by_request.values():
        gaps.extend(b - a for a, b in zip(stamps, stamps[1:]))
    return gaps


def ttft_per_request(due: dict, first_stamp: dict) -> list[float]:
    """First-token stamp minus the time the request was DUE (open
    loop), for every request due in the window. One whose first token
    had not come when the window closed counts as infinite: it missed
    any limit, and under overload it pulls the median up instead of
    dropping out of it."""
    return [
        first_stamp[rid] - t_due if rid in first_stamp else math.inf
        for rid, t_due in due.items()
    ]


def whole_dispatches(events, t_open: float, t_close: float):
    """The dispatches (t_end, tokens) that ended inside the window,
    in time order. `events` is every call of `_admit` or `_tick` the
    loop made that emitted a token: the clock when it returned and the
    tokens stamped during it."""
    return sorted(
        (t, n) for t, n in events if t_open < t <= t_close and n > 0
    )


def rate_between(events, i: int, j: int) -> float:
    """Tokens stamped in (t_i, t_j] over (t_j - t_i): the tokens of
    dispatches i+1..j, each counted whole, over exactly the time those
    dispatches took. No partial dispatch at either edge."""
    if j <= i:
        raise ValueError("need two different dispatch ends")
    tokens = sum(n for _, n in events[i + 1 : j + 1])
    return tokens / (events[j][0] - events[i][0])


def tokens_per_s(events) -> float:
    """Tokens per second over whole dispatches: from the end of the
    first dispatch in the window to the end of the last, all the work
    and all the time between. A count in a fixed window would move in
    steps of one tick's tokens (32 at a full batch) with where the
    window's edges fall inside a tick; this does not."""
    if len(events) < 2:
        raise ValueError("fewer than two dispatches in the window")
    return rate_between(events, 0, len(events) - 1)


def slice_rates(events, slice_s: float = SLICE_S) -> list[float]:
    """The window cut into consecutive slices of about `slice_s`
    seconds whose edges are ends of dispatches; the rate of each."""
    edges = [0]
    for k, (t, _) in enumerate(events):
        if t - events[edges[-1]][0] >= slice_s:
            edges.append(k)
    return [rate_between(events, a, b) for a, b in zip(edges, edges[1:])]


def median(values) -> float:
    return percentile(values, 50.0)
