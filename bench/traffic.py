"""One generator for every traffic mix: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) names a loop (``closed`` or ``open``)
and the distributions of prompt length and ``max_new``. Sizes and
inter-arrival gaps are stratified: for ``n`` requests the generator takes
the quantiles ``(i + 0.5) / n`` of each distribution and lets ``--seed``
only permute them and draw the token ids. So every seed offers the same
set of sizes and arrivals, in another order, and runs of different seeds
do the same work.

With ``"order": "fixed"`` the order is drawn from a fixed stream instead,
so every seed offers the same schedule and the seed draws only the token
ids (and the weights): for a tail that the order of a few long requests
would otherwise move from seed to seed.

Distributions: ``{"dist": "uniform", "min": a, "max": b}`` (integers, both
ends included), ``{"dist": "lognormal", "median": m, "sigma": s, "min": a,
"max": b}`` (rounded, clipped), ``{"dist": "fixed", "value": v}``.
"""

from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Req:
    """One request as the client sends it. ``at`` is the scheduled send
    time in seconds from the start of the schedule (open loop only)."""
    prompt: list
    max_new: int
    at: float = 0.0
    warm: bool = False  # a closed-loop warm-start request (set-up, not window)


def load_mix(path) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return mix


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for one stream of one seed (any size of seed)."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def ppf(spec: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a length distribution at ``u`` (integers)."""
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(len(u), int(spec["value"]), np.int64)
    if kind == "uniform":
        lo, hi = int(spec["min"]), int(spec["max"])
        return np.minimum(lo + np.floor(u * (hi - lo + 1)), hi).astype(np.int64)
    if kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in u])
        v = np.round(float(spec["median"]) * np.exp(float(spec["sigma"]) * z))
        return np.clip(v, int(spec["min"]), int(spec["max"])).astype(np.int64)
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """The ``n`` quantile points of ``spec``, in an order drawn from ``rng``."""
    return rng.permutation(ppf(spec, quantiles(n)))


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> list:
    return rng.integers(1, vocab, size=int(n)).tolist()


def _check(reqs, max_len: int) -> None:
    for r in reqs:
        if not (0 < len(r.prompt) < max_len - 1 and r.max_new >= 1
                and len(r.prompt) + r.max_new <= max_len):
            raise ValueError(f"request of {len(r.prompt)} prompt tokens and "
                             f"max_new {r.max_new} does not fit max_len "
                             f"{max_len}")


def order_rng(mix: dict, seed: int) -> np.random.Generator:
    """The generator that orders the stratified sizes and gaps."""
    return rng_for(0 if mix.get("order") == "fixed" else seed, 1)


def closed_plan(mix: dict, *, clients: int, seed: int, vocab: int,
                max_len: int) -> list[list[Req]]:
    """Per client, the requests it sends one after another.

    With ``warm_start`` each client first sends one request that stands
    for a stream already under way when the window opens: a request of
    output length ``n`` (drawn with weight ``n``, as a busy slot holds
    long requests longer) at an age ``a`` drawn uniformly from ``[0, n)``,
    sent as a prompt of ``p + a`` tokens with ``max_new = n - a``. The
    context mix is then steady when the window opens, without a ramp of
    several request lifetimes."""
    per = int(mix["requests_per_client"])
    n = clients * per
    rng = order_rng(mix, seed)
    plen = stratified(mix["prompt_tokens"], n, rng)
    mnew = stratified(mix["max_new"], n, rng)
    plans = [[] for _ in range(clients)]
    tok_rng = rng_for(seed, 2)
    for j in range(n):
        plans[j % clients].append(
            Req(_tokens(tok_rng, plen[j], vocab), int(mnew[j])))
    if mix.get("warm_start"):
        pool = np.sort(ppf(mix["max_new"], quantiles(n)))
        cdf = np.cumsum(pool) / pool.sum()
        u = rng.permutation(quantiles(clients))
        ages = rng.permutation(quantiles(clients))
        p0 = stratified(mix["prompt_tokens"], clients, rng)
        for i in range(clients):
            out = int(pool[min(np.searchsorted(cdf, u[i]), n - 1)])
            age = int(ages[i] * out)
            plans[i].insert(0, Req(_tokens(tok_rng, p0[i] + age, vocab),
                                   out - age, warm=True))
    _check([r for p in plans for r in p], max_len)
    return plans


def open_plan(mix: dict, *, rate: float, seconds: float, seed: int,
              vocab: int, max_len: int) -> list[Req]:
    """Requests at scheduled times over ``ramp_s + seconds``: Poisson
    arrivals at ``rate`` per second, with stratified gaps."""
    span = float(mix.get("ramp_s", 0.0)) + float(seconds)
    n = max(1, int(math.ceil(rate * span)))
    rng = order_rng(mix, seed)
    gaps = -np.log1p(-rng.permutation(quantiles(n))) / float(rate)
    at = np.cumsum(gaps) - gaps[0]
    plen = stratified(mix["prompt_tokens"], n, rng)
    mnew = stratified(mix["max_new"], n, rng)
    tok_rng = rng_for(seed, 2)
    reqs = [Req(_tokens(tok_rng, plen[j], vocab), int(mnew[j]), float(at[j]))
            for j in range(n)]
    _check(reqs, max_len)
    return reqs
