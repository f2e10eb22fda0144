"""One general traffic generator: a data file of parameters -> requests.

A traffic file (``benchmarks/traffic/<mix>.json``) gives the loop (open at
a fixed rate, or closed with a client count), the length distributions and
optional burst windows. Adding a mix is adding a file.

Every seed gets the SAME set of (prompt, output) sizes and the same set of
arrival gaps, in another order: sizes are the stratified quantiles of the
file's distributions (not draws), paired by a shuffle keyed on the file's
own ``pool_seed``; the run's seed only permutes the order and picks the
prompts' characters. So two seeds differ in order and content, never in
the amount of work — a seed that changed the work would read as noise.

Open loop: ``n = round(rate * seconds)`` arrivals exactly. Their gaps are
the stratified quantiles of the exponential distribution, permuted by the
seed and scaled to fill the window: a Poisson process conditioned on its
count. ``burst_windows`` [[at_s, duration_s, multiplier], ...] bend the
clock through the inverse of the cumulative rate (the exact-count form of
loadlab/arrival.py's thinning), compounding where they overlap.

Closed loop: clients take requests from one list as they come free. The
list is blocks of ``block`` requests; each block is the same set of sizes
in another order, so however far a run gets it has seen whole sets.

No JAX, no numpy: the load generator's process imports this.
"""

from __future__ import annotations

import math
import random
import statistics
from typing import Any

_NORMAL = statistics.NormalDist()
# printable ASCII without the quote and the backslash: one byte, one
# ByteTokenizer token, and nothing JSON has to escape
_ALPHABET = [chr(c) for c in range(32, 127) if chr(c) not in '"\\']


def stratified_sizes(dist: dict[str, Any], n: int) -> list[int]:
    """The n mid-quantiles of a clipped log-normal (or a constant)."""
    kind = dist.get("dist", "lognormal")
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "constant":
        return [max(lo, min(hi, int(dist["value"])))] * n
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}")
    mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(max(lo, min(hi, int(round(math.exp(mu + sigma * z))))))
    return out


def size_pairs(spec: dict[str, Any], n: int) -> list[tuple[int, int]]:
    """The fixed set of (prompt_tokens, max_tokens) pairs of this mix at
    this count: the pairing depends on the file, not on the run's seed."""
    prompts = stratified_sizes(spec["prompt_tokens"], n)
    outputs = stratified_sizes(spec["output_tokens"], n)
    random.Random(f"bench:pairs:{spec.get('pool_seed', 0)}:{n}").shuffle(outputs)
    return list(zip(prompts, outputs))


def _warp(spec: dict[str, Any], seconds: float, u: float) -> float:
    """Map operational time u in [0, 1) to clock time through the inverse
    cumulative rate of the burst windows (piecewise constant)."""
    windows = [(float(a), float(d), float(m)) for a, d, m in spec.get("burst_windows", [])]
    if not windows:
        return u * seconds
    edges = sorted({0.0, seconds, *(min(max(e, 0.0), seconds)
                                    for a, d, _ in windows for e in (a, a + d))})
    segs = []
    for a, b in zip(edges, edges[1:]):
        mid, mult = (a + b) / 2, 1.0
        for at, dur, m in windows:
            if at <= mid < at + dur:
                mult *= m
        segs.append((a, b, mult))
    total = sum((b - a) * m for a, b, m in segs)
    target, acc = u * total, 0.0
    for a, b, m in segs:
        mass = (b - a) * m
        if acc + mass > target and m > 0:
            return a + (target - acc) / m
        acc += mass
    return seconds


def arrival_offsets(spec: dict[str, Any], seconds: float, seed: int) -> list[float]:
    """n = round(rate*seconds) offsets in [0, seconds), Poisson in shape."""
    n = int(round(float(spec["rate_per_s"]) * seconds))
    if n <= 0:
        return []
    gaps = [-math.log(1.0 - (i + 0.5) / (n + 1)) for i in range(n + 1)]
    random.Random(f"bench:arrivals:{seed}").shuffle(gaps)
    total = sum(gaps)
    out, t = [], 0.0
    for g in gaps[:n]:  # the last gap is the one after the final arrival
        t += g
        out.append(_warp(spec, seconds, t / total))
    return out


def _prompt_text(rng: random.Random, tokens: int) -> str:
    """tokens - 1 characters: the ByteTokenizer adds BOS."""
    return "".join(rng.choices(_ALPHABET, k=max(tokens - 1, 1)))


def generate(spec: dict[str, Any], seed: int, seconds: float) -> dict[str, Any]:
    """The run's schedule: ``{"loop", "clients", "requests": [...]}`` with
    each request ``{"index", "due" (open loop only, seconds from window
    start), "prompt", "prompt_tokens", "max_tokens"}``."""
    loop = spec["loop"]
    order = random.Random(f"bench:order:{seed}")
    text = random.Random(f"bench:text:{seed}")
    requests: list[dict[str, Any]] = []
    if loop == "open":
        offsets = arrival_offsets(spec, seconds, seed)
        pairs = size_pairs(spec, len(offsets))
        order.shuffle(pairs)
        for i, (due, (p, o)) in enumerate(zip(offsets, pairs)):
            requests.append({"index": i, "due": due, "prompt": _prompt_text(text, p),
                             "prompt_tokens": p, "max_tokens": o})
        clients = 0
    elif loop == "closed":
        clients = int(spec["clients"])
        block = int(spec.get("block", 32))
        # more than any window can use: the generator stops at the window's end
        blocks = int(spec.get("blocks", max(4, math.ceil(seconds * float(spec.get("max_requests_per_s", 4.0)) / block))))
        base = size_pairs(spec, block)
        for b in range(blocks):
            pairs = list(base)
            order.shuffle(pairs)
            for p, o in pairs:
                requests.append({"index": len(requests), "prompt": _prompt_text(text, p),
                                 "prompt_tokens": p, "max_tokens": o})
    else:
        raise ValueError(f"traffic loop must be open or closed, not {loop!r}")
    return {"loop": loop, "clients": clients, "requests": requests}


def longest_shapes(spec: dict[str, Any]) -> dict[str, int]:
    """The mix's extremes, for sizing the warm-up and the reference."""
    return {"prompt_min": int(spec["prompt_tokens"]["min"]),
            "prompt_max": int(spec["prompt_tokens"]["max"]),
            "output_max": int(spec["output_tokens"]["max"])}

