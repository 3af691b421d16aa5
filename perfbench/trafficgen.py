"""The one general traffic generator: reads a traffic file of kind
``open_loop`` or ``backlog`` and makes the requests of one run.

What a run's work is comes from the FILE (its ``shape_seed``): the
(prompt length, output length) pairs, the inter-arrival gaps and the order
of both. The run's ``--seed`` draws only the token ids (and, in the
runner, the weights): measured on the chip (PR 23), the same work in
another order moved the steady cell's p90 TTFT by 10% between seeds
against 0.1% between two runs of one seed, so the order is part of the mix.

Arrival arithmetic as in the program's ``obs/loadgen.py``: i.i.d.
exponential gaps with mean 1/rate, cumulated (a Poisson process), kept
while they fall inside the window. Lengths are log-normal (median, sigma)
clipped to [lo, hi]."""

from __future__ import annotations

import math

import numpy as np


def _lognormal(rng, n, spec):
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(int)


def shape(traffic: dict, seconds: float):
    """The run-independent part: gaps and length pairs."""
    rng = np.random.default_rng([int(traffic["shape_seed"]), 1])
    if traffic["kind"] == "open_loop":
        rate = float(traffic["rate_rps"])
        n_max = int(rate * seconds * 2 + 50)
        gaps = rng.exponential(1.0 / rate, size=n_max)
        n = int(np.searchsorted(np.cumsum(gaps), seconds))
        gaps = gaps[:n]
    elif traffic["kind"] == "backlog":
        n = int(math.ceil(traffic["requests_per_second_offered"] * seconds))
        gaps = np.zeros(n)
    else:
        raise ValueError(traffic["kind"])
    # a backlog is only partly served, so its lengths are a small set
    # (``cycle`` pairs) offered over and over: whatever prefix of the queue
    # a run gets through holds the same mix
    m = int(traffic.get("cycle", n)) or n
    prompts = _lognormal(rng, min(m, n), traffic["prompt_tokens"])
    outputs = _lognormal(rng, min(m, n), traffic["output_tokens"])
    return gaps, prompts, outputs


def requests(traffic: dict, seconds: float, seed: int, vocab: int) -> list:
    """[{arrival_s, tokens, max_new}] in arrival order."""
    gaps, prompts, outputs = shape(traffic, seconds)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    m = len(prompts)
    orng = np.random.default_rng([int(traffic["shape_seed"]), 4])
    order = np.concatenate([orng.permutation(m)
                            for _ in range(-(-len(gaps) // m))])[:len(gaps)]
    gaps = gaps[orng.permutation(len(gaps))]
    arrivals = np.cumsum(gaps)
    if traffic["kind"] == "open_loop" and len(arrivals):
        # the same gaps in another order sum to the same span
        arrivals = np.minimum(arrivals, np.nextafter(seconds, 0))
    ramp = traffic.get("ramp")
    out = []
    for j, i in enumerate(order):
        t = float(arrivals[j])
        if ramp:
            # the first ramp["requests"] are spaced, the rest are due when
            # the ramp ends: no admission wave is wider than the chip holds
            t = min(j, ramp["requests"]) * float(ramp["gap_s"])
        toks = rng.integers(1, vocab, size=int(prompts[i]))
        out.append({"arrival_s": t, "tokens": [int(x) for x in toks],
                    "max_new": int(outputs[i])})
    return out
