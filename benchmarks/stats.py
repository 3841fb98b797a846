"""Metric arithmetic, as pure functions over plain numbers. No JAX, no
clock: the cells record, these reduce, the tests check them."""
import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear interpolation between order statistics (numpy's default);
    ``q`` in [0, 100]. None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return percentile(values, 50.0)


def slowest_tenth_mean(values: Sequence[float]) -> Optional[float]:
    """Mean of the slowest tenth (at least one) of the values."""
    xs = sorted(values)
    if not xs:
        return None
    k = max(1, math.ceil(len(xs) / 10))
    return sum(xs[-k:]) / k


# ------------------------------------------------------------- training
def window_tokens_per_s(step_ends: Sequence[float], tokens_per_step: int
                        ) -> Optional[float]:
    """All the window's tokens over all its time. ``step_ends[0]`` is
    the instant the window opened (the last warm-up step's end), the
    rest the instant each measured step's results were ready."""
    if len(step_ends) < 2:
        return None
    return tokens_per_step * (len(step_ends) - 1) \
        / (step_ends[-1] - step_ends[0])


def block_tokens_per_s(step_ends: Sequence[float], tokens_per_step: int,
                       block: int) -> List[float]:
    """tokens/s of each consecutive whole block of ``block`` steps."""
    out = []
    for i in range(0, len(step_ends) - block, block):
        out.append(tokens_per_step * block
                   / (step_ends[i + block] - step_ends[i]))
    return out


def step_times(step_ends: Sequence[float]) -> List[float]:
    return [b - a for a, b in zip(step_ends, step_ends[1:])]


# -------------------------------------------------------------- serving
# A request record (times in seconds from the window's start):
#   due     when the schedule said to send it (closed loop: when sent)
#   sent    when the generator did send it
#   tokens  arrival time of each generated token at the client
#   error   None, or what went wrong
# A request counts in a window's tails if it was DUE inside the window.

def due_in_window(requests: Sequence[Dict], window_s: float) -> List[Dict]:
    return [r for r in requests if 0.0 <= r["due"] < window_s]


def ttft_s(req: Dict, window_s: float, listen_s: float) -> float:
    """Time to first token from the instant the request was due. The
    run goes on listening after the window (until ``listen_s``) for the
    first tokens of requests due inside it; one that failed, or has none
    even then, counts at the window's length: never better than any
    request that was answered."""
    if is_failed(req, listen_s):
        return window_s
    return req["tokens"][0] - req["due"]


def is_failed(req: Dict, listen_s: float) -> bool:
    """Failed: an error, or no first token by the time the run stopped
    listening."""
    return bool(req.get("error")) or not any(
        t <= listen_s for t in req["tokens"][:1])


def ttfts_ms(requests: Sequence[Dict], window_s: float, listen_s: float
             ) -> List[float]:
    return [1e3 * ttft_s(r, window_s, listen_s)
            for r in due_in_window(requests, window_s)]


def tpots_ms(requests: Sequence[Dict], window_s: float) -> List[float]:
    """Per request due in the window with at least two tokens by its
    end: (last - first token time) / (n - 1)."""
    out = []
    for r in due_in_window(requests, window_s):
        toks = [t for t in r["tokens"] if t <= window_s]
        if len(toks) >= 2:
            out.append(1e3 * (toks[-1] - toks[0]) / (len(toks) - 1))
    return out


def tokens_in_window(requests: Sequence[Dict], window_s: float) -> int:
    """Generated tokens whose arrival at the client falls inside
    [0, window_s): counted token by token, whatever request they belong
    to and whether or not it completed."""
    return sum(1 for r in requests for t in r["tokens"]
               if 0.0 <= t < window_s)


def completed_tokens(requests: Sequence[Dict], window_s: float) -> int:
    """Tokens of requests that got all they asked for inside the window
    (the count PR 22 judged, kept as a per-layer view)."""
    return sum(len(r["tokens"]) for r in requests
               if len(r["tokens"]) >= r["asked"]
               and 0.0 <= r["tokens"][-1] < window_s)


def in_flight_at(requests: Sequence[Dict], t: float) -> int:
    """Requests due by ``t`` and not finished (or failed) by ``t``."""
    n = 0
    for r in requests:
        if r["due"] > t:
            continue
        done = len(r["tokens"]) >= r["asked"] and r["tokens"][-1] <= t
        if not done and not r.get("error"):
            n += 1
    return n


def longest_silence(requests: Sequence[Dict], window_s: float
                    ) -> List[float]:
    """[seconds, start] of the longest stretch of the window in which
    no token of any request arrived: for the log, so that a run that
    reads far off shows whether one stall did it."""
    times = sorted(t for r in requests for t in r["tokens"]
                   if 0 <= t < window_s)
    edges = [0.0] + times + [float(window_s)]
    gap, at = max((b - a, a) for a, b in zip(edges, edges[1:]))
    return [gap, at]

