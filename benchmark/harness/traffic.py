"""The one general traffic generator: a traffic file's parameters and a
seed give a schedule. Pure Python, no numpy, no jax (the load generator
child imports this and must never touch the chip).

Every seed offers the SAME requests: the same sizes at the same arrival
times in the same order, drawn once from the traffic file's
``base_seed`` as stratified quantiles of the stated distributions.
``--seed`` chooses the texts (and, in run.py, the weights): two seeds
differ in content, never in the amount of work, the offered rate or
which burst meets which request. A seed that changes the work reads as
noise in every later check (measured on the chip, PR 23: with sizes
reordered per seed inside blocks of 4 requests, cell 1's p95 TTFT ran
459 to 669 ms from seed to seed, and within 5-12% for one seed).

A schedule is a list of requests, each a dict:
    i           index in due order
    due_s       open loop: seconds after the window opens; closed: None
    prompt_len  prompt tokens, BOS included (byte tokenizer: 1 per char)
    output_len  max_tokens (ignore_eos: exactly this many come back)
    prefix      index of the shared prefix, or None
"""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import List, Optional

_WORDS = ("alpha bravo charlie delta echo foxtrot golf hotel india "
          "juliet kilo lima mike november oscar papa quebec romeo "
          "sierra tango uniform victor whiskey xray yankee zulu").split()


def quantile(dist: dict, u: float) -> int:
    """Inverse CDF of a length distribution at u in (0, 1), clipped."""
    kind = dist["dist"]
    if kind == "fixed":
        return int(dist["value"])
    if kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        x = dist["median"] * math.exp(
            dist["sigma"] * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def _stratified(dist: dict, n: int, rng: random.Random) -> List[int]:
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    rng.shuffle(vals)
    return vals


def _zipf_assign(count: int, s: float, n: int, rng: random.Random) -> list:
    """n prefix indices with shares proportional to 1/rank**s (largest
    remainders, so the multiset is fixed), shuffled."""
    w = [1.0 / (r + 1) ** s for r in range(count)]
    exact = [n * x / sum(w) for x in w]
    got = [int(e) for e in exact]
    for r in sorted(range(count), key=lambda r: exact[r] - got[r],
                    reverse=True)[:n - sum(got)]:
        got[r] += 1
    out = [r for r in range(count) for _ in range(got[r])]
    rng.shuffle(out)
    return out


def n_requests(params: dict, seconds: float) -> int:
    if params["loop"] == "open":
        return max(int(round(params["rate_rps"] * seconds)), 1)
    return int(params["pool"])


def schedule(params: dict, seconds: float) -> List[dict]:
    """The requests of one run, in due order (closed loop: in the order
    the clients take them); the same for every seed."""
    n = n_requests(params, seconds)
    base = random.Random(int(params["base_seed"]))
    prompts = _stratified(params["prompt_len"], n, base)
    outputs = _stratified(params["output_len"], n, base)
    sp = params.get("shared_prefix")
    prefixes: List[Optional[int]] = (
        _zipf_assign(sp["count"], sp["zipf"], n, base) if sp else [None] * n)
    gaps: List[float] = []
    if params["loop"] == "open":
        # exponential quantiles, scaled so the gaps fill the window
        # exactly: n arrivals at the stated rate, as a cycle
        gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        base.shuffle(gaps)
        scale = seconds / sum(gaps)
        gaps = [g * scale for g in gaps]

    due: List[Optional[float]] = [None] * n
    if gaps:
        t, due = 0.0, []
        for g in gaps:      # first arrival at 0: the window opens on it
            due.append(t)
            t += g
    return [{"i": i, "due_s": due[i], "prompt_len": prompts[i],
             "output_len": outputs[i], "prefix": prefixes[i]}
            for i in range(n)]


def filler(rng: random.Random, nchars: int) -> str:
    """Seeded ASCII filler of exactly nchars characters."""
    out, n = [], -1          # n = length of " ".join(out)
    while n < nchars:
        w = _WORDS[rng.randrange(len(_WORDS))]
        out.append(w)
        n += len(w) + 1
    return " ".join(out)[:nchars]


def messages(params: dict, seed: int, req: dict) -> List[dict]:
    """The chat messages of one request. With ``ext.use_raw_prompt`` the
    server concatenates their texts and prepends BOS, so the characters
    here number ``prompt_len - 1``."""
    rng = random.Random(f"{seed}/{req['i']}")
    chars = req["prompt_len"] - 1
    sp = params.get("shared_prefix")
    if sp is None or req["prefix"] is None:
        return [{"role": "user", "content": filler(rng, chars)}]
    prefix = filler(random.Random(f"{seed}/prefix/{req['prefix']}"),
                    sp["chars"])
    return [{"role": "system", "content": prefix},
            {"role": "user", "content": filler(rng, chars - sp["chars"])}]
