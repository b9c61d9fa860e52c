"""Open-loop traffic from a mix file and a seed.

A run has three phases on one time axis, in seconds from the opening of
the measured window: ``warm`` (due in ``[-warm_s, 0)``, part of set-up),
``window`` (due in ``[0, seconds)``, the requests measured) and ``drain``
(due after the window, offered only to keep the load steady while the
window's requests finish).

Every phase holds ``round(rate * length)`` requests. Their lengths and gaps
are stratified quantiles of the mix's distributions, so every seed gets
the same set of sizes and gaps; the seed only orders them and pairs
prompts with outputs. The lengths are the lognormal body-plus-tail mix of
the program's ``repro.workload.profiles.sample_lengths``; the gaps are
exponential (Poisson arrivals).

Each request is clipped to one cache slot of ``max_len`` positions: the
prompt to ``max_len - min(output, max_len // 2)``, then the output to
``max_len - prompt``.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

PHASES = ("warm", "window", "drain")


@dataclasses.dataclass(frozen=True)
class Planned:
    rid: int
    due: float           # seconds from the window's opening
    prompt_len: int
    output_len: int
    phase: str


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / max(n, 1)


def _lognormal(median: float, sigma: float, u: np.ndarray) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    return median * np.exp(sigma * z)


def stratified_lengths(mix: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The ``n`` prompt and output lengths every seed shares, unordered."""
    n_tail = int(round(mix.get("tail_frac", 0.0) * n))
    body = _lognormal(mix["body_median"], mix["body_sigma"],
                      _quantiles(n - n_tail))
    tail = _lognormal(mix.get("tail_median", 1.0), mix.get("tail_sigma", 0.0),
                      _quantiles(n_tail))
    prompts = np.clip(np.concatenate([body, tail]), mix["min_input"],
                      mix["max_input"]).astype(int)
    outputs = np.clip(_lognormal(mix["out_median"], mix["out_sigma"],
                                 _quantiles(n)),
                      mix["min_output"], mix["max_output"]).astype(int)
    return prompts, outputs


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` stratified exponential gaps of mean ``1/rate``."""
    return -np.log1p(-_quantiles(n)) / rate


def clip(prompt: int, output: int, max_len: int) -> tuple[int, int]:
    prompt = min(prompt, max_len - min(output, max_len // 2))
    return prompt, min(output, max_len - prompt)


def plan(traffic: dict, max_len: int, seed: int,
         seconds: float) -> list[Planned]:
    """Every request of a run, in due order, with unique ``rid``s."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    rate = float(traffic["rate_rps"])
    spans = {"warm": (-float(traffic["warm_s"]), 0.0),
             "window": (0.0, float(seconds)),
             "drain": (float(seconds), float(seconds)
                       + float(traffic["drain_s"]))}
    out: list[Planned] = []
    for i, phase in enumerate(PHASES):
        lo, hi = spans[phase]
        n = int(round(rate * (hi - lo)))
        if n == 0:
            continue
        rng = np.random.default_rng([seed, i])
        prompts, outputs = stratified_lengths(traffic["mix"], n)
        prompts = rng.permutation(prompts)
        outputs = rng.permutation(outputs)
        gaps = rng.permutation(exponential_gaps(n, rate))
        # n arrivals spread over the phase: the first at its start, each
        # later one a (rescaled) gap after the previous
        starts = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        dues = lo + starts * ((hi - lo) / gaps.sum())
        for p, o, due in zip(prompts, outputs, dues):
            cp, co = clip(int(p), int(o), max_len)
            out.append(Planned(len(out), float(due), cp, co, phase))
    return out


def clipped_share(mix: dict, max_len: int, n: int = 1000) -> float:
    """Share of requests that ``clip`` shortens (the same for every seed)."""
    prompts, outputs = stratified_lengths(mix, n)
    return float(np.mean([clip(int(p), int(o), max_len) != (int(p), int(o))
                          for p, o in zip(prompts, outputs)]))


def prompt_tokens(rid: int, prompt_len: int, vocab: int) -> np.ndarray:
    """The prompt's token ids, drawn as the served executor draws them."""
    rng = np.random.default_rng(rid)
    return rng.integers(0, vocab, size=prompt_len).astype(np.int32)
