"""The one traffic generator. A mix is a data file, ``traffic/<mix>.json``;
this module turns it and a seed into the inputs of a run. Every mix has
the same keys, so a new mix is a new file and never new code:

- ``arrivals``: a list of phases ``{"rate_rps": r, "seconds": d}``. Timed
  requests arrive open loop (on a schedule fixed before the run, whatever
  the system does) as a Poisson process whose rate cycles through the
  phases in order, each lasting its ``seconds``. One phase is a plain
  Poisson process (its ``seconds`` is then irrelevant); an on/off pair is
  a bursty mix; an empty list sends no timed requests.
- ``keep_queued``: before every engine step, requests are added until at
  least this many are outstanding (closed loop; an offline job keeping
  its queue full). 0 for a pure open-loop mix.
- ``sizes``: images per request, P(k) proportional to ``k ** -exponent``
  on ``[min, max]`` (``min == max`` gives one fixed size).
- ``image_pool``: images drawn N(0, 1) float32 from the seed, on the
  device, that requests take consecutive slices of.
- ``check_requests``: how many served requests the check samples.

Every seed gets the same multiset of sizes and of arrival gaps (the
distributions' quantiles), in its own order, so that seeds change which
request comes when, not how much work a run holds.
"""

from __future__ import annotations

import json
import math
import pathlib

import numpy as np

MIX_DIR = pathlib.Path(__file__).resolve().parent / "traffic"
IMAGE_SHAPE = (32, 32, 3)
REFILL_BLOCK = 4096   # closed-loop sizes are drawn in blocks this long


def load(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2**64, stream])


def size_pmf(sizes: dict) -> tuple[np.ndarray, np.ndarray]:
    """Support and probabilities of the request-size law."""
    if sizes.get("law", "power") != "power":
        raise ValueError(f"unknown size law {sizes['law']!r}")
    ks = np.arange(sizes["min"], sizes["max"] + 1)
    p = ks.astype(np.float64) ** -float(sizes["exponent"])
    return ks, p / p.sum()


def mean_size(mix: dict) -> float:
    ks, p = size_pmf(mix["sizes"])
    return float((ks * p).sum())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def draw_sizes(mix: dict, seed: int, n: int, stream: int) -> np.ndarray:
    """``n`` request sizes: the law's ``n`` quantiles in a seeded order."""
    ks, p = size_pmf(mix["sizes"])
    idx = np.searchsorted(np.cumsum(p), _quantiles(n))
    return _rng(seed, stream).permutation(ks[np.minimum(idx, len(ks) - 1)])


def _intensity(phases: list, seconds: float):
    """Breakpoints ``(t, Lambda(t))`` of the cumulative arrival intensity
    over ``[0, seconds]``, the phases cycling in order."""
    ts, lam = [0.0], [0.0]
    if len(phases) == 1:
        return np.array([0.0, seconds]), np.array(
            [0.0, float(phases[0]["rate_rps"]) * seconds])
    j = 0
    while ts[-1] < seconds:
        ph = phases[j % len(phases)]
        if float(ph["seconds"]) <= 0:
            raise ValueError(f"phase {ph} lasts no time")
        d = min(float(ph["seconds"]), seconds - ts[-1])
        ts.append(ts[-1] + d)
        lam.append(lam[-1] + float(ph["rate_rps"]) * d)
        j += 1
    return np.asarray(ts), np.asarray(lam)


def schedule(mix: dict, seed: int, seconds: float):
    """``(due_s, sizes)`` of the timed requests: when each is due, in
    seconds from the window's start, and its number of images. The gaps
    are unit-rate exponential quantiles in a seeded order, mapped through
    the inverse of the phases' cumulative intensity."""
    phases = mix.get("arrivals") or []
    if not phases:
        return np.zeros(0), np.zeros(0, np.int64)
    ts, lam = _intensity(phases, seconds)
    n = max(1, math.ceil(lam[-1]))
    gaps = _rng(seed, 2).permutation(-np.log1p(-_quantiles(n)))
    at = np.cumsum(gaps) - gaps[0]
    # the segment each arrival falls in: past any idle (flat) stretch
    k = np.clip(np.searchsorted(lam, at, side="right") - 1, 0, len(lam) - 2)
    rise = np.maximum(lam[k + 1] - lam[k], 1e-300)
    due = ts[k] + (at - lam[k]) * (ts[k + 1] - ts[k]) / rise
    sizes = draw_sizes(mix, seed, n, 1)
    keep = at < lam[-1]
    return due[keep], sizes[keep]


def refill_sizes(mix: dict, seed: int):
    """The sizes of the closed-loop requests, in order, endlessly."""
    block = draw_sizes(mix, seed, REFILL_BLOCK, 4)
    while True:
        yield from (int(k) for k in block)


def dispatch_rows(mix: dict, max_rows: int) -> list[int]:
    """The row counts a dispatch of this mix can have, for warm-up. Only
    a mix with no timed requests, one request size, and a queue that
    always holds whole dispatches fills every dispatch; any other can
    leave a partial one behind the batcher's wait."""
    ks, _ = size_pmf(mix["sizes"])
    rows = int(mix.get("keep_queued", 0)) * int(ks[0])
    if (not mix.get("arrivals") and len(ks) == 1 and rows >= max_rows
            and rows % max_rows == 0):
        return [max_rows]
    return list(range(1, max_rows + 1))


def image_pool(mix: dict, seed: int) -> np.ndarray:
    """The run's images, ``[image_pool, 32, 32, 3]`` float32 on the host,
    drawn on the device from the seed in one call."""
    import jax

    from bench.reference import seed_key

    key = jax.random.fold_in(seed_key(seed), 0x1A6E)
    shape = (int(mix["image_pool"]),) + IMAGE_SHAPE
    return np.asarray(jax.jit(jax.random.normal, static_argnums=1)(key, shape))


class PoolCursor:
    """Hands out consecutive slices of the pool, restarting at its head
    when a request would run past the end."""

    def __init__(self, pool: np.ndarray):
        self.pool = pool
        self.at = 0

    def take(self, k: int) -> tuple[int, np.ndarray]:
        if self.at + k > len(self.pool):
            self.at = 0
        lo = self.at
        self.at += k
        return lo, self.pool[lo:lo + k]
