"""Traffic generation: one general generator, driven by a traffic file.

A traffic mix is a JSON file of parameters under ``benchmark/traffic/``; a
cell (``benchmark/cells/<cell>.json``) adds the rate or the client count. No
mix needs code of its own: loop kind, arrival process, length distributions
and prefix sharing are all parameters here.

What the seed changes, and what it does not. Every seed gets THE SAME set of
(gap, prompt length, output length) triples in THE SAME cyclic order — they
are drawn once from the mix's own ``shape_seed`` — because in this system
which requests sit next to each other in the queue decides how they are
batched, so a reshuffle per seed would change the work, not sample it. The
seed chooses the token ids of every prompt and nothing else. The open-loop
schedule is periodic with the measured window as its period, so a window
holds each request of the cycle exactly once.

Lengths are stratified: the i-th of N values is the distribution's quantile
at (i + 0.5) / N, then put in a fixed shuffled order. A dozen requests then
stand for the distribution as well as a dozen can.
"""

from __future__ import annotations

import dataclasses
import statistics
from typing import Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as the generator planned it. ``due_s`` is relative to the
    start of the run's ramp (open loop); None in a closed loop, where a
    request is due when its client's last one completes."""

    index: int
    due_s: Optional[float]
    prompt: np.ndarray  # int32 ids
    max_new: int
    client: int = 0  # closed loop: which client sends it


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` stratified draws of a length distribution, in shuffled order."""
    kind = spec["dist"]
    if kind == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(q) for q in _quantiles(n)])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        vals = spec["min"] + (spec["max"] - spec["min"]) * _quantiles(n)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    vals = np.clip(np.rint(vals), spec.get("min", 1), spec.get("max", 1 << 30))
    return rng.permutation(vals.astype(np.int64))


def _gaps(arrivals: dict, n: int, period_s: float,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps that sum to ``period_s`` exactly."""
    process = arrivals.get("process", "poisson")
    if process == "poisson":
        g = rng.permutation(-np.log1p(-_quantiles(n)))
    elif process == "gamma":  # bursty: coefficient of variation > 1
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = rng.gamma(shape, 1.0 / shape, size=n)
    elif process == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    return g * (period_s / g.sum())


class Shape:
    """The seed-independent part of a mix at one size: the cycle of
    (offset, prompt length, output length, sharing group) entries."""

    def __init__(self, traffic: dict, n: int, period_s: float = 0.0):
        rng = np.random.default_rng(int(traffic["shape_seed"]))
        self.n = n
        self.prompt_len = _lengths(traffic["prompt_len"], n, rng)
        self.output_len = _lengths(traffic["output_len"], n, rng)
        if period_s > 0:
            gaps = _gaps(traffic.get("arrivals", {}), n, period_s, rng)
            self.offset_s = np.cumsum(gaps) - gaps[0]
        else:
            self.offset_s = None
        sharing = traffic.get("sharing", {"kind": "none"})
        self.sharing = sharing
        kind = sharing["kind"]
        if kind == "none":
            self.group = None
        elif kind in ("shared_prefix", "sessions"):
            self.group = np.arange(n) % int(sharing["groups"])
            self.prefix_len = _lengths(
                sharing["prefix_len"], int(sharing["groups"]), rng
            )
        else:
            raise ValueError(f"unknown sharing kind {kind!r}")


class _Prompts:
    """Token ids from the run's seed. Shared prefixes and session histories
    are kept per group so that later requests repeat them exactly."""

    def __init__(self, shape: Shape, vocab: int, seed: int):
        self.shape = shape
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed), 0x70726F6D])
        self.history: dict[int, np.ndarray] = {}
        if shape.group is not None:
            for g, n in enumerate(shape.prefix_len):
                self.history[g] = self._ids(int(n))

    def _ids(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def make(self, k: int, max_prompt: int) -> np.ndarray:
        own = self._ids(int(self.shape.prompt_len[k]))
        if self.shape.group is None:
            return own
        g = int(self.shape.group[k])
        prompt = np.concatenate([self.history[g], own])
        if self.shape.sharing["kind"] == "sessions":
            # the next turn of this session repeats this whole prompt and a
            # reply of the planned length; when it would outgrow the limit
            # the session starts over from its system prompt
            grown = np.concatenate(
                [prompt, self._ids(int(self.shape.output_len[k]))]
            )
            limit = max_prompt - int(self.shape.prompt_len.max())
            self.history[g] = (
                grown if len(grown) <= limit
                else grown[: int(self.shape.prefix_len[g])]
            )
        return prompt[:max_prompt]


def open_schedule(traffic: dict, rate_rps: float, period_s: float,
                  horizon_s: float, vocab: int, seed: int,
                  max_prompt: int = 1 << 30) -> list[Planned]:
    """Every request due in ``[0, horizon_s)`` after the ramp's start. The
    cycle has ``round(rate × period)`` requests and repeats every
    ``period_s``; the measured window is ``[ramp_s, ramp_s + period_s)``."""
    n = max(1, round(rate_rps * period_s))
    shape = Shape(traffic, n, period_s)
    prompts = _Prompts(shape, vocab, seed)
    out: list[Planned] = []
    lap, k = 0, 0
    while True:
        if k == n:
            lap, k = lap + 1, 0
        due = float(shape.offset_s[k]) + lap * period_s
        if due >= horizon_s:
            return out
        out.append(Planned(
            index=len(out), due_s=due, prompt=prompts.make(k, max_prompt),
            max_new=int(shape.output_len[k]),
        ))
        k += 1


class ClosedClients:
    """Closed loop: ``clients`` callers, each sending its next request when
    its last one completes. Requests are taken from the cycle in order,
    from its start; the seed picks the token ids."""

    def __init__(self, traffic: dict, clients: int, vocab: int, seed: int,
                 max_prompt: int = 1 << 30):
        n = int(traffic.get("cycle_requests", 256))
        self.shape = Shape(traffic, n)
        self.clients = int(clients)
        self.max_prompt = max_prompt
        self._prompts = _Prompts(self.shape, vocab, seed)
        self._count = 0

    def next(self, client: int) -> Planned:
        k = self._count % self.shape.n
        p = Planned(
            index=self._count, due_s=None,
            prompt=self._prompts.make(k, self.max_prompt),
            max_new=int(self.shape.output_len[k]), client=client,
        )
        self._count += 1
        return p


def reachable_buckets(traffic: dict, buckets, max_prompt: int) -> list[int]:
    """The admit buckets this mix's prompt lengths can reach — what set-up
    has to warm. Computed from the mix's bounds, not from one sample."""
    lo = int(traffic["prompt_len"].get("min", 1))
    hi = int(traffic["prompt_len"].get("max", max_prompt))
    sharing = traffic.get("sharing", {"kind": "none"})
    if sharing["kind"] != "none":
        # a radix hit admits only the suffix, a miss the whole prompt
        hi = max_prompt
    hi = min(hi, max_prompt)
    reach, prev = [], 0
    for b in buckets:
        if b >= lo and prev < hi:
            reach.append(int(b))
        prev = b
    return reach
