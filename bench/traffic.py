"""Closed-loop traffic from a mix file: the one generator every mix uses.

A mix file (``bench/traffic/<name>.json``) states the lengths as lognormal
distributions clipped to a range, and the number of clients. The request
list is built from the file alone:

* One round holds one request per client. Its output lengths are the
  lognormal's quantiles at evenly spaced probabilities (i + 0.5) / clients,
  clipped and rounded. Its prompt lengths take ``levels`` distinct values,
  the quantiles at (j + 0.5) / levels, each rounded up to ``multiple`` and
  clipped, and each used clients / levels times. Prompt and output are
  paired by a fixed permutation, so that the two are uncorrelated and the
  pairs are the same in every round.
* A round is served as clients / levels groups, each holding one request
  of every prompt length, so that any stretch of the stream admits the
  prompt lengths in the same proportions.
* Rounds follow one another for as long as the run asks for requests.

The run's seed then deals each length's pairs to the groups, shuffles the
order inside each group and draws the token ids; it never changes a length.
So every seed serves the same multiset of (prompt, output) pairs, and the
first round, which fills the pool, is the same multiset for every seed.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
# the fixed permutation that pairs prompt lengths with output lengths
PAIRING_SEED = 0
# what a mix file may hold: the generator's parameters and their sources;
# any other key would be a knob that nothing honours
MIX_KEYS = {"name", "loop", "clients", "prompt", "output", "source",
            "assumed", "why"}


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    clients: int
    prompt: dict
    output: dict

    @property
    def max_prompt(self) -> int:
        return max(prompt_levels(self))

    @property
    def max_output(self) -> int:
        return max(output_lengths(self))


def load_mix(name: str, directory: str = os.path.join(HERE, "traffic")
             ) -> Mix:
    with open(os.path.join(directory, f"{name}.json")) as f:
        spec = json.load(f)
    unknown = set(spec) - MIX_KEYS
    if unknown:
        raise ValueError(f"mix {name}: unknown keys {sorted(unknown)}")
    if spec.get("loop") != "closed":
        raise ValueError(f"mix {name}: only closed-loop mixes with zero "
                         f"think time are generated")
    mix = Mix(name=name, clients=int(spec["clients"]),
              prompt=spec["prompt"], output=spec["output"])
    if mix.clients % int(mix.prompt["levels"]):
        raise ValueError(f"mix {name}: clients must be a multiple of the "
                         f"prompt levels")
    return mix


def _quantile(dist: dict, p: float) -> float:
    z = NormalDist().inv_cdf(p)
    return float(dist["median"]) * math.exp(float(dist["sigma"]) * z)


def _clip(x: int, dist: dict) -> int:
    return int(min(max(x, int(dist["min"])), int(dist["max"])))


def prompt_levels(mix: Mix) -> List[int]:
    d = mix.prompt
    n, mult = int(d["levels"]), int(d["multiple"])
    out = []
    for j in range(n):
        q = _quantile(d, (j + 0.5) / n)
        out.append(_clip(int(math.ceil(q / mult) * mult), d))
    if len(set(out)) != n:
        raise ValueError(f"mix {mix.name}: prompt levels collide: {out}")
    return out


def output_lengths(mix: Mix) -> List[int]:
    d = mix.output
    n = mix.clients
    return [_clip(int(round(_quantile(d, (i + 0.5) / n))), d)
            for i in range(n)]


def round_pairs(mix: Mix) -> List[tuple]:
    """The (prompt, output) pairs of one round, in a fixed order."""
    levels = prompt_levels(mix)
    prompts = [levels[i % len(levels)] for i in range(mix.clients)]
    perm = np.random.default_rng(PAIRING_SEED).permutation(mix.clients)
    outs = output_lengths(mix)
    return [(prompts[int(perm[i])], outs[i]) for i in range(mix.clients)]


@dataclasses.dataclass
class Draw:
    """One request of the list: its lengths and token ids."""
    index: int
    prompt: np.ndarray       # (T,) int32
    max_new_tokens: int


class RequestStream:
    """The run's requests in serving order, drawn a round at a time."""

    def __init__(self, mix: Mix, seed: int, vocab: int):
        self._rng = np.random.default_rng(seed)
        self._pairs = round_pairs(mix)
        self._levels = prompt_levels(mix)
        self._vocab = vocab
        self._queue: List[Draw] = []
        self.drawn = 0

    def take(self, n: int) -> List[Draw]:
        return [self.next() for _ in range(n)]

    def next(self) -> Draw:
        if not self._queue:
            self.draw_round()
        return self._queue.pop(0)

    def round_order(self) -> List[tuple]:
        """One round's pairs in serving order: group after group, each
        group one pair of every prompt length, in an order from the seed."""
        by_level = [[p for p in self._pairs if p[0] == T]
                    for T in self._levels]
        for pairs in by_level:
            self._rng.shuffle(pairs)
        order = []
        for group in zip(*by_level):
            order.extend(group[int(k)] for k in
                         self._rng.permutation(len(group)))
        return order

    def draw_round(self) -> None:
        for T, n_out in self.round_order():
            toks = self._rng.integers(0, self._vocab, size=T, dtype=np.int32)
            self._queue.append(Draw(index=self.drawn, prompt=toks,
                                    max_new_tokens=n_out))
            self.drawn += 1


def length_multiset(draws: List[Draw]) -> List[tuple]:
    return sorted((int(d.prompt.shape[0]), d.max_new_tokens) for d in draws)
