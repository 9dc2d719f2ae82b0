"""The one traffic generator: a mix file's parameters -> seeded requests.

A mix (``bench/mixes/<traffic>.json``) states the arrival process and
the length distributions.  Every seed gets the same multiset of sizes
and inter-arrival gaps — stratified quantiles of the mix's
distributions — and the seed chooses their order and the token ids.

Arrival processes:
  * ``poisson`` — open loop at ``rate_per_s``.  The schedule is cut
    into consecutive segments (lead, measured window, drain); each
    segment of ``L`` seconds holds ``floor(rate * L)`` requests with
    their own stratified gaps and lengths.  So the window offers the
    same requests on every seed, in another order.
  * ``closed`` — ``clients`` callers, each sending its next request as
    soon as its last one finished; every client starts at time 0.  Each
    client's first request keeps only a stratified share
    ``(i + 0.5) / clients`` of its output length, as if the loop had been
    running: the first completions are spread evenly, and so are the
    next prefills.

Length distributions (``prompt``, ``output``): ``lognormal`` with a
``median`` and ``sigma``, clipped to ``[min, max]``; an output may be
rounded up to ``plus + round_up_to * k`` tokens.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional, Sequence

import numpy as np

_NORMAL = NormalDist()


def seed_streams(seed: int, n: int = 4) -> List[np.random.SeedSequence]:
    """Independent child seed streams of one run seed (any size of int):
    traffic order, token ids, weights/data, check sample."""
    return np.random.SeedSequence(int(seed)).spawn(n)


def jax_seed(stream: np.random.SeedSequence) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey`` (which silently
    truncates ints wider than 32 bits)."""
    return int(stream.generate_state(1, np.uint32)[0]) & 0x7FFFFFFF


def strata(n: int) -> np.ndarray:
    """Midpoints of ``n`` equal-probability strata of (0, 1)."""
    return (np.arange(n) + 0.5) / n


def _round(spec: dict, x: np.ndarray) -> np.ndarray:
    if "round_up_to" in spec:
        step, plus = spec["round_up_to"], spec.get("plus", 0)
        return plus + step * np.ceil(np.maximum(x - plus, 0) / step)
    return np.rint(x)


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of a length spec, ascending."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([_NORMAL.inv_cdf(u) for u in strata(n)])
    x = _round(spec, spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified exponential inter-arrival gaps at ``rate``/s."""
    return -np.log1p(-strata(n)) / rate


@dataclasses.dataclass
class Item:
    """One request of the schedule.  ``due`` is seconds from the start
    of the arrival process (open loop) or None (closed loop: due when
    the client's previous request finished)."""
    rid: int
    prompt_len: int
    max_tokens: int
    due: Optional[float] = None
    client: int = 0


def _sizes(mix: dict, n: int, order) -> tuple:
    p = order.permutation(lengths(mix["prompt"], n)) \
        if "prompt" in mix else np.zeros(n, np.int64)
    o = order.permutation(lengths(mix["output"], n)) \
        if "output" in mix else np.zeros(n, np.int64)
    return p, o


def schedule(mix: dict, seed: int, segments: Sequence[float]) -> List[Item]:
    """Requests of ``mix`` for ``seed``.  Open loop: arrivals over the
    consecutive ``segments`` (seconds each: lead, window, drain).  Closed
    loop: the clients' queues (``segments`` is not used)."""
    order = np.random.default_rng(seed_streams(seed)[0])
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        rate = float(arr["rate_per_s"])
        due, p, o = [], [], []
        start = 0.0
        for span in segments:
            n = math.floor(rate * span)
            if n:
                g = order.permutation(gaps(rate, n))
                due.append(start + np.concatenate([[0.0], np.cumsum(g)[:-1]]))
                pk, ok = _sizes(mix, n, order)
                p.append(pk)
                o.append(ok)
            start += span
        due, p, o = (np.concatenate(x) for x in (due, p, o))
        clients = np.zeros(len(due), np.int64)
    elif arr["kind"] == "closed":
        c = int(arr["clients"])
        n = c * int(arr["requests_per_client"])
        due = None
        clients = np.arange(n) % c
        p, o = _sizes(mix, n, order)
        spec = mix["output"]
        share = order.permutation(strata(c))
        least = spec.get("plus", 0) + spec.get("round_up_to", 1)
        o[:c] = np.maximum(_round(spec, share * o[:c]), least)
    else:
        raise ValueError(f"unknown arrival process {arr['kind']!r}")
    return [Item(i, int(p[i]), int(o[i]),
                 None if due is None else float(due[i]), int(clients[i]))
            for i in range(len(p))]


def token_ids(seed: int, items: List[Item], vocab: int) -> List[np.ndarray]:
    """Uniform random prompt token ids for each item, from the seed."""
    rng = np.random.default_rng(seed_streams(seed)[1])
    return [rng.integers(0, vocab, it.prompt_len, dtype=np.int32)
            for it in items]
