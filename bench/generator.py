"""The one traffic generator: turns a mix file (``bench/traffic/<mix>.json``)
and a seed into the requests of one run.

A mix is data only. An offline batch (``"kind": "backlog"``) is a list of
requests that carry no arrival time; the client keeps a queue of them.
Every seed gets the same sizes at the same places, in another order, so
that every run's window serves the same work: the prompt and output
lengths are the stratified quantiles ``F^-1((i + 0.5) / b)``, ``i < b``,
of their distributions, in blocks of the mix's ``block``. A block's
quantiles, in increasing order, fall into strata of ``stratum``
neighbours; the strata take their places in a block by a fixed
low-discrepancy (Kronecker) order, each length its own, so that every
stretch of a block holds short and long requests alike; the seed permutes
the requests within each stratum. Token ids are drawn from the seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    prompt: np.ndarray
    max_new: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of the distribution ``spec`` describes,
    in increasing order, clipped to its ``min``/``max``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(v, spec["min"], spec["max"])


# the Kronecker step of each length's order of strata: two irrationals
# with no rational relation, so the pairs of strata cover their square
STEP = {"prompt_tokens": (5 ** 0.5 - 1) / 2, "output_tokens": 2 ** 0.5 - 1}


def strata_order(n: int, step: float) -> np.ndarray:
    """The stratum at each of ``n`` places: the rank of ``k * step mod
    1`` among ``k < n``."""
    return np.argsort(np.argsort((np.arange(n) * step) % 1.0,
                                 kind="stable"), kind="stable")


def block_layout(spec: dict, b: int, g: int, step: float) -> np.ndarray:
    """A block's sizes by place, before the seed's permutation: row ``k``
    holds the stratum at the ``g`` places ``g * k ... g * k + g - 1``."""
    if b % g:
        raise ValueError(f"block {b} is not a multiple of stratum {g}")
    strata = np.rint(quantiles(spec, b)).astype(np.int64).reshape(-1, g)
    return strata[strata_order(b // g, step)]


def generate(mix: dict, seed: int, vocab: int, horizon_s: float
             ) -> List[Request]:
    """Requests enough to keep the backlog full for ``horizon_s``."""
    if mix["kind"] != "backlog":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    rng = np.random.default_rng(np.random.SeedSequence(seed % 2 ** 64))
    n = int(mix["backlog_requests_per_s"] * horizon_s + 4 * mix["max_batch"])
    b, g = int(mix["block"]), int(mix["stratum"])
    sizes = []
    for k in ("prompt_tokens", "output_tokens"):
        lay = block_layout(mix[k], b, g, STEP[k])
        sizes.append(np.concatenate([
            rng.permutation(row) for _ in range(-(-n // b))
            for row in lay])[:n])
    return [Request(rng.integers(1, vocab, int(p)).astype(np.int32), int(o))
            for p, o in zip(*sizes)]


def max_len(mix: dict) -> int:
    """The longest trajectory (prompt + output) the mix can produce: the
    engine's ``max_len``, so only the mix's own shapes compile."""
    return int(mix["prompt_tokens"]["max"]) + int(mix["output_tokens"]["max"])
