"""Closed-loop chat traffic from a mix file, ``perfbench/traffic/<name>.json``.

One general generator reads every mix.  A mix names its kind and its length
bounds; the seed picks the tokens and the order of the lengths, never the
lengths themselves.  Each length comes from a fixed set of strata (the
midpoints of ``STRATA`` equal-probability bins of a log-uniform law between
the bounds), and every block of ``STRATA`` consecutive draws of one stream
holds each stratum once, in an order drawn from the seed.  So two seeds give
the same work in another order, and any stretch of a stream's requests is
close to the declared distribution.

Kinds:

* ``single`` -- every request is a fresh prompt of uniformly random token
  ids (no page-aligned prefix is shared with any other request).  All
  clients draw from one stream: the k-th request sent, by whichever client,
  is the stream's k-th.  A window's requests then make whole blocks of the
  one stream but for its two ends, where a stream per client would give a
  part of each client's block, and a work that changes with the seed;
* ``sessions`` -- a client runs multi-turn sessions back to back.  A session
  opens with one of ``system_prompts`` fixed system prompts of
  ``system_tokens`` tokens, chosen uniformly, and lasts a number of turns
  drawn from ``turns`` (inclusive).  Each turn's prompt is the previous
  turn's prompt, the answer served to it, and a new user turn.

Mix keys besides the kind: ``clients``, ``prompt_tokens`` or ``user_tokens``
and ``output_tokens`` as ``[lo, hi]`` bounds, and ``engine``: the serving
engine's options for the cell (``max_batch``, ``max_len``, ``page_size``,
``prefix_cache``).
"""
from __future__ import annotations

import json
import os
from typing import List, Optional, Sequence, Tuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
STRATA = 16


def load_mix(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def strata(lo: int, hi: int, n: int = STRATA) -> np.ndarray:
    """The ``n`` bin midpoints of a log-uniform law on ``[lo, hi]``."""
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(np.log(lo) + q * (np.log(hi) - np.log(lo)))).astype(int)


class _Lengths:
    """Endless stratified draws: each block of STRATA holds every stratum."""

    def __init__(self, rng: np.random.Generator, bounds: Sequence[int]):
        self.rng = rng
        self.values = strata(*bounds)
        self.block: List[int] = []

    def __call__(self) -> int:
        if not self.block:
            self.block = self.rng.permutation(self.values).tolist()
        return int(self.block.pop())


class Stream:
    """One client's requests.  ``next(previous)`` gives the next prompt and
    its output budget; ``previous`` is ``(prompt, served tokens)`` of the
    client's last request, or None for its first."""

    def __init__(self, mix: dict, vocab: int, seed: int, client: int,
                 systems: Optional[List[List[int]]]):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, client])
        self.out = _Lengths(self.rng, mix["output_tokens"])
        if mix["kind"] == "single":
            self.prompt = _Lengths(self.rng, mix["prompt_tokens"])
        else:
            self.user = _Lengths(self.rng, mix["user_tokens"])
            self.turns_left = 0
        self.systems = systems

    def _tokens(self, n: int) -> List[int]:
        return self.rng.integers(0, self.vocab, n).tolist()

    def next(self, previous: Optional[Tuple[List[int], List[int]]]
             ) -> Tuple[List[int], int]:
        if self.mix["kind"] == "single":
            return self._tokens(self.prompt()), self.out()
        if self.turns_left == 0 or previous is None:
            lo, hi = self.mix["turns"]
            self.turns_left = int(self.rng.integers(lo, hi + 1))
            head = list(self.systems[int(self.rng.integers(len(self.systems)))])
        else:
            prompt, served = previous
            head = list(prompt) + list(served)
        self.turns_left -= 1
        return head + self._tokens(self.user()), self.out()


def streams(mix: dict, vocab: int, seed: int) -> List[Stream]:
    """The stream of each client of ``mix``, all drawn from ``seed`` (for
    ``single``, one stream that every client shares)."""
    systems = None
    if mix["kind"] == "sessions":
        rng = np.random.default_rng([seed, 1 << 20])
        systems = [rng.integers(0, vocab, mix["system_tokens"]).tolist()
                   for _ in range(mix["system_prompts"])]
    elif mix["kind"] == "single":
        return [Stream(mix, vocab, seed, 0, None)] * mix["clients"]
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return [Stream(mix, vocab, seed, c, systems) for c in range(mix["clients"])]


def length_bounds(mix: dict) -> Tuple[int, int]:
    """Least and greatest prompt a mix can send (for warming shapes)."""
    if mix["kind"] == "single":
        lo, hi = strata(*mix["prompt_tokens"])[[0, -1]]
        return int(lo), int(hi)
    users = strata(*mix["user_tokens"])
    outs = strata(*mix["output_tokens"])
    turns = mix["turns"][1]
    lo = mix["system_tokens"] + int(users[0])
    hi = (mix["system_tokens"] + turns * int(users[-1])
          + (turns - 1) * int(outs[-1]))
    return lo, hi
