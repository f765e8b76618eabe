"""Whether what the timed path served is right: the comparison that decides
``correct``.

``Tap`` wraps the executor the engine drives.  When the clients send a
request it decides, from the seed, whether to keep that request's logits:
always when the request is the longest (prompt plus output budget) sent so
far, so the run's longest request is among them, and otherwise with
probability ``KEEP_SHARE``.  For a kept request it holds, on the device, the
logits row behind each token the engine samples: the prefill's row as the
program returned it, and each decode step's row sliced out by one small
program warmed in set-up.  Nothing is read back inside the window.

After the window, a sample of the kept requests that finished is drawn from
the seed: the longest, then others in a seeded order, until
``SAMPLE_TOKENS`` served tokens or ``SAMPLE_MAX_REQUESTS`` requests are in
it.  A plain float32 forward of the family's reference runs once over each
sampled prompt followed by its served tokens.  The numbers compared:

* ``logit_err`` -- at each position that produced a served token, the
  largest gap between the program's logit and the reference's over the
  vocabulary, in standard deviations of the reference's row; the widest over
  the sample.  Limit: the configuration's ``check.logit_err``;
* ``off_argmax`` -- served tokens that are not the first of their own row
  (the traffic is greedy), limit 0;
* ``unmatched`` -- kept requests whose rows do not match their tokens one
  for one, limit 0.

With the served token its row's best, a ``logit_err`` of e bounds the gap by
which the served token's reference logit lies below the reference's best to
2e; that gap is printed, not compared.

The control (``--control 1``; not part of a benchmark run) puts the same
reference, computed with every matmul operand in the configuration's
``check.control`` precision, in the program's place: its logits, and the
tokens it puts first.  It is judged by the same limits and must come out
not correct.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np
from jax import lax

KEEP_SHARE = 0.1
SAMPLE_TOKENS = 300
SAMPLE_MAX_REQUESTS = 8


@jax.jit
def _row(logits, i):
    """Row ``i`` of a decode step's (B, V) logits, as (1, V)."""
    return lax.dynamic_slice_in_dim(logits, i, 1)


@jax.jit
def _column(logits, j):
    """Row ``j`` of a chunk prefill's (1, S, V) logits, as (1, V)."""
    return lax.dynamic_index_in_dim(logits, j, axis=1, keepdims=False)


class Tap:
    """Executor wrapper keeping the kept requests' logits rows on the device.

    Before ``engine`` is set (set-up's warm-up) every call runs the slicing
    programs and keeps nothing, so they are compiled before the window.  The
    engine admits the request it has just taken from its queue
    (``clients.Inbox.taken``) into the one active slot the tap does not
    know; a decode step's row ``i`` belongs to slot ``i``."""

    def __init__(self, inner, seed: int, clients: int):
        self.inner = inner
        self.engine = None
        self.rows: Dict[int, List] = {}   # kept uid -> device rows (1, V)
        self._slots: Dict[int, object] = {}
        self._longest = 0
        self._rngs = [np.random.default_rng([seed, 17, c])
                      for c in range(clients)]

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def choose(self, r, client: int) -> None:
        size = len(r.prompt) + r.max_new_tokens
        longest = size >= self._longest
        self._longest = max(self._longest, size)
        if self._rngs[client].random() < KEEP_SHARE or longest:
            self.rows[r.uid] = []

    def _admitted(self, row) -> None:
        if self.engine is None:
            return
        for s in [s for s, r in self._slots.items() if r.done]:
            del self._slots[s]
        new = [int(s) for s in np.flatnonzero(self.engine.pool.active)
               if s not in self._slots]
        if len(new) != 1:
            raise RuntimeError(f"a prefill with {len(new)} new slots")
        r = self.engine.queue.taken
        self._slots[new[0]] = r
        if r.uid in self.rows:
            self.rows[r.uid].append(row)

    def prefill_paged(self, tokens, pool, block_row, length):
        logits, pool = self.inner.prefill_paged(tokens, pool, block_row,
                                                length)
        self._admitted(logits)
        return logits, pool

    def prefill_chunk(self, tokens, pool, block_row, *, offset, length):
        logits, pool = self.inner.prefill_chunk(tokens, pool, block_row,
                                                offset=offset, length=length)
        if offset <= length - 1 < offset + tokens.shape[1]:
            self._admitted(_column(logits, length - 1 - offset))
        return logits, pool

    def decode_paged(self, tokens, pool, block_table, positions):
        logits, pool = self.inner.decode_paged(tokens, pool, block_table,
                                               positions)
        if self.engine is None:
            _row(logits, 0)
        for s, r in self._slots.items():
            if not r.done and r.uid in self.rows:
                self.rows[r.uid].append(_row(logits, s))
        return logits, pool


def sample(requests, seed: int) -> List:
    if not requests:
        return []
    longest = max(requests, key=lambda r: (len(r.prompt) + len(r.output),
                                           -r.uid))
    rest = [r for r in requests if r is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    picked, tokens = [longest], len(longest.output)
    for i in order:
        if tokens >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX_REQUESTS:
            break
        picked.append(rest[i])
        tokens += len(rest[i].output)
    return picked


def readings(ref: np.ndarray, got: np.ndarray, served: np.ndarray) -> dict:
    """The numbers of logits ``got`` and tokens ``served`` against the
    reference's rows ``ref``."""
    sd = ref.std(axis=1)
    rows = np.arange(len(served))
    err = np.abs(got - ref).max(axis=1) / sd
    gap = (ref.max(axis=1) - ref[rows, served]) / sd
    return {"logit_err": float(err.max()),
            "off_argmax": int((got.argmax(axis=1) != served).sum()),
            "served_gap": float(gap.max())}


def compare(family, conf: dict, weights, requests, rows: Dict[int, List],
            seed: int, *, max_len: int, control: bool = False) -> dict:
    limits = conf["check"]
    kept = [r for r in requests if r.uid in rows and r.done and r.output]
    unmatched = sum(len(rows[r.uid]) != len(r.output) for r in kept)
    picked = sample([r for r in kept if len(rows[r.uid]) == len(r.output)],
                    seed)
    if not picked:
        nothing = {"logit_err": {"value": None, "limit": limits["logit_err"]}}
        return {"correct": False, "checks": nothing}
    toks = np.zeros((len(picked), max_len), np.int32)
    at, served = [], []
    for i, r in enumerate(picked):
        seq = r.prompt + r.output[:-1]
        toks[i, :len(seq)] = seq
        at += [(i, len(r.prompt) - 1 + j) for j in range(len(r.output))]
        served += r.output
    at = np.asarray(at, np.int32)
    served = np.asarray(served)
    got = np.concatenate([np.concatenate(jax.device_get(rows[r.uid]))
                          for r in picked])
    ref = family.reference_logits(conf, weights, toks, at)
    values = readings(ref, got, served)
    print(f"check: {len(picked)} of {len(kept)} kept requests, {len(served)} "
          f"served tokens, longest {len(picked[0].prompt)} + "
          f"{len(picked[0].output)}; program {values}", flush=True)
    if control:
        low = family.reference_logits(conf, weights, toks, at,
                                      precision=limits["control"])
        program, values = values, readings(ref, low, low.argmax(axis=1))
        print(f"check: control ({limits['control']}) {values}", flush=True)
    checks = {"logit_err": {"value": values["logit_err"],
                            "limit": limits["logit_err"]},
              "off_argmax": {"value": values["off_argmax"], "limit": 0},
              "unmatched": {"value": unmatched, "limit": 0}}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    if control:
        checks["program_logit_err"] = {"value": program["logit_err"],
                                       "limit": limits["logit_err"]}
    return {"correct": correct, "checks": checks}
