"""Seeded draws for traffic. The lengths and gaps of a mix are a FIXED set, the
stratified quantiles of its distributions, put in an order that the traffic
file's ``schedule_seed`` fixes; the run's ``--seed`` draws the token ids, the
sampling seeds and the weights. Every seed then gives the system the same sizes
at the same times with other contents, so runs of different seeds differ no
more than runs of one. (Ordering by ``--seed`` was tried first, PR 23: the 95th
percentile of time to first token then spread by 12 % between seeds, because
where the few long prompts fall among the arrivals decides the tail.)

The distributions are those of ``datatunerx_tpu/loadgen/workload.py``
(``base x Pareto(alpha)`` capped, adapters weighted ``1/rank**s``, exponential
gaps); that file draws characters for HTTP replay, this one token counts.
"""

from __future__ import annotations

import math

import numpy as np


def mix_seed(seed: int, tag: int) -> int:
    """A 63-bit stream id from any whole-number seed (the driver's are above 2**31)."""
    return (int(seed) * 0x9E3779B97F4A7C15 + int(tag) * 0xBF58476D1CE4E5B9) % (1 << 63)


def rng_for(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng(mix_seed(seed, tag))


def pareto_quantiles(n: int, spec: dict) -> np.ndarray:
    """n whole numbers: the (i + 0.5)/n quantiles of min * Pareto(alpha), capped at max."""
    if spec.get("dist") != "pareto":
        raise ValueError(f"unknown length distribution {spec!r}")
    u = (np.arange(n) + 0.5) / n
    x = spec["min"] * (1.0 - u) ** (-1.0 / spec["alpha"])
    return np.minimum(np.floor(x), spec["max"]).astype(np.int64)


def exponential_quantiles(n: int, mean: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log(1.0 - u)
    return gaps * (mean / gaps.mean())


def zipf_counts(n: int, names: list, s: float, base_share: float) -> list:
    """n adapter names, '' for the base: base_share of them to the base, the rest
    over ``names`` with weight 1/rank**s, by largest remainder so the set is fixed."""
    if not names:
        return [""] * n
    n_base = int(round(n * base_share))
    w = np.array([1.0 / (r + 1) ** s for r in range(len(names))])
    exact = (n - n_base) * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: (n - n_base) - counts.sum()]:
        counts[i] += 1
    out = [""] * n_base
    for name, c in zip(names, counts):
        out += [name] * int(c)
    return out


def request_set(n: int, traffic: dict, adapters: list, vocab: int, seed: int) -> list:
    """n requests of the mix: sizes, adapters and which are greedy in the order
    the traffic file's schedule_seed fixes; token ids and sampling seeds from ``seed``."""
    order = rng_for(int(traffic["schedule_seed"]), 1)
    prompts = order.permutation(pareto_quantiles(n, traffic["prompt_tokens"]))
    outputs = order.permutation(pareto_quantiles(n, traffic["output_tokens"]))
    names = zipf_counts(n, adapters, float(traffic.get("adapter_zipf_s", 0.0)),
                        float(traffic.get("base_share", 1.0)))
    names = [names[i] for i in order.permutation(n)]
    n_greedy = int(math.ceil(n * float(traffic.get("greedy_share", 0.0))))
    greedy = np.zeros(n, bool)
    greedy[order.permutation(n)[:n_greedy]] = True
    rng = rng_for(seed, 1)
    reqs = []
    for i in range(n):
        reqs.append({
            "id": i,
            "prompt": rng.integers(10, vocab, size=int(prompts[i])).tolist(),
            "max_new_tokens": int(outputs[i]),
            "adapter": names[i],
            "temperature": 0.0 if greedy[i] else float(traffic["temperature"]),
            "top_p": float(traffic.get("top_p", 1.0)),
            "seed": int(rng.integers(0, 2**31 - 1)),
        })
    return reqs


def arrival_times(n: int, rate: float, schedule_seed: int) -> np.ndarray:
    gaps = rng_for(schedule_seed, 2).permutation(exponential_quantiles(n, 1.0 / rate))
    return np.cumsum(gaps) - gaps[0]
