"""Seeded open-loop traffic from a mix file (``traffic/<mix>.json``).

One general generator reads every mix.  Every seed asks for the same work in
an order of its own: the same multiset of prompt lengths, of answer lengths
and of inter-arrival gaps, each permuted by the seed on its own, with prompt
token ids of its own.  The sizes are the quantiles of the mix's
distributions at ``n`` evenly spaced points, the gaps those of the
exponential distribution (Poisson arrivals at ``rate_per_s``), scaled so
that the ``n`` gaps fill the window exactly.

The arrivals form a cycle as long as the window, repeated: the ``lead_in_s``
before the window replay the cycle's end, with prompts of their own, so the
window opens in the cycle's steady state and holds every request of the
cycle once.

Size distributions (``prompt_tokens``, ``output_tokens``): ``lognormal``
(``median``, ``sigma``), ``uniform_int`` (``min``, ``max``) or ``choice``
(``values``), clipped to ``min``/``max`` and rounded up to ``round_up_to``.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass
class Req:
    rid: str
    prompt: np.ndarray  # (S,) int32 token ids
    max_new: int
    due: float | None = None  # seconds after traffic starts


def quantile(dist: dict, u: float) -> int:
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    elif kind == "uniform_int":
        x = dist["min"] + math.floor(u * (dist["max"] - dist["min"] + 1))
    elif kind == "choice":
        vals = dist["values"]
        x = vals[min(len(vals) - 1, math.floor(u * len(vals)))]
    else:
        raise ValueError(f"unknown size distribution {kind!r}")
    x = min(max(x, dist.get("min", x)), dist.get("max", x))
    r = dist.get("round_up_to", 1)
    return int(math.ceil(round(x, 6) / r) * r)


def support(dist: dict) -> list[int]:
    """Every size the distribution can give, ascending."""
    if dist["dist"] == "choice":
        return sorted({quantile(dist, (i + 0.5) / len(dist["values"]))
                       for i in range(len(dist["values"]))})
    r = dist.get("round_up_to", 1)
    lo = quantile(dist, 1e-12)
    hi = quantile(dist, 1 - 1e-12)
    return list(range(lo, hi + 1, r))


def cycle(mix: dict, seconds: float, rng: np.random.Generator) -> list[tuple[float, int, int]]:
    """The cycle of ``rate * seconds`` arrivals: (offset, prompt, answer),
    offsets in ``[0, seconds)``.  The multisets are the same for every seed;
    ``rng`` orders each."""
    if mix["arrivals"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']['process']!r}")
    n = max(1, round(mix["arrivals"]["rate_per_s"] * seconds))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    gaps *= seconds / gaps.sum()
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    prompts = rng.permutation([quantile(mix["prompt_tokens"], x) for x in u])
    outs = rng.permutation([quantile(mix["output_tokens"], x) for x in u])
    return [(float(t), int(p), int(o)) for t, p, o in zip(offsets, prompts, outs)]


def _prompt(rng: np.random.Generator, vocab: int, length: int) -> np.ndarray:
    return rng.integers(1, vocab, length).astype(np.int32)


def open_loop(mix: dict, seconds: float, seed: int, vocab: int, tag: str = "r") -> list[Req]:
    """The seed's cycle over ``lead_in_s`` and then the window's ``seconds``
    (which opens at ``lead_in_s``).  Every arrival has prompt ids of its
    own.  Request ids are ``tag`` and the arrival's index."""
    rng = np.random.default_rng(seed)
    arrivals = cycle(mix, seconds, rng)
    lead = mix.get("lead_in_s", 0.0)
    due = [(lead + k * seconds + t, p, o)
           for k in range(-math.ceil(lead / seconds), 1) for t, p, o in arrivals]
    due = [a for a in due if a[0] >= 0.0]
    return [Req(f"{tag}{i}", _prompt(rng, vocab, p), o, t)
            for i, (t, p, o) in enumerate(due)]


def warmup(mix: dict, vocab: int, max_len: int, page: int) -> list[Req]:
    """Requests that make the engine compile every shape this mix can reach,
    to be sent one at a time: each prompt length alone (one prefill shape
    each) with two tokens out, then whatever block-table widths only longer
    answers reach."""
    def width(tokens: int) -> int:  # the engine's power-of-two page coverage
        need, n = -(-tokens // page), 1
        while n < need:
            n *= 2
        return min(n, max_len // page)

    rng = np.random.default_rng(0)
    prompts = support(mix["prompt_tokens"])
    longest = max(support(mix["output_tokens"]))
    reqs = [Req(f"w{p}", _prompt(rng, vocab, p), 2) for p in prompts]
    covered = {width(p + 1) for p in prompts} | {width(p + 2) for p in prompts}
    needed = {width(p + 1 + k) for p in prompts for k in range(longest)}
    for w in sorted(needed - covered):
        pos = (w // 2) * page  # first position whose decode runs at width w
        p = max(q for q in prompts if width(q + 1) < w)
        reqs.append(Req(f"w{p}.{w}", _prompt(rng, vocab, p), pos - p + 2))
    return reqs
