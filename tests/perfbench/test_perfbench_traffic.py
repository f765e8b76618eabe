"""The benchmark's traffic generator: seeded, stratified, and sharing exactly
what each mix declares."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench import traffic  # noqa: E402

SEED = 3_000_000_019  # beyond 32 signed bits, as the benchmark's seeds are
# the sessions mix a later cell would run, as its data file would give it
SESSIONS = {"kind": "sessions", "clients": 8, "system_prompts": 4,
            "system_tokens": 1024, "turns": [1, 2], "user_tokens": [32, 256],
            "output_tokens": [16, 256],
            "engine": {"max_batch": 8, "max_len": 2048, "page_size": 256,
                       "prefix_cache": True}}


def _mix(name):
    return SESSIONS if name == "sessions-2k" else traffic.load_mix(name)


def _drive(mix, seed, n=40, vocab=1000):
    """Each client's first ``n`` requests, answering every prompt with
    ``max_new`` fresh tokens (served tokens feed the next session turn)."""
    rng = np.random.default_rng(0)
    out = []
    for s in traffic.streams(mix, vocab, seed):
        prev, reqs = None, []
        for _ in range(n):
            prompt, new = s.next(prev)
            served = rng.integers(0, vocab, new).tolist()
            reqs.append((prompt, new, served))
            prev = (prompt, served)
        out.append(reqs)
    return out


@pytest.mark.parametrize("name", ["chat-1k", "sessions-2k"])
def test_same_seed_same_streams_and_lengths_in_bounds(name):
    mix = _mix(name)
    a, b = _drive(mix, SEED), _drive(mix, SEED)
    assert [[(p, n) for p, n, _ in c] for c in a] == \
        [[(p, n) for p, n, _ in c] for c in b]
    c = _drive(mix, SEED + 1)
    assert [p for p, _, _ in a[0]] != [p for p, _, _ in c[0]]
    lo, hi = traffic.length_bounds(mix)
    for client in a:
        for prompt, new, _ in client:
            assert lo <= len(prompt) <= hi
            assert len(prompt) + new <= mix["engine"]["max_len"]


def test_seeds_change_the_order_of_lengths_not_the_lengths():
    mix = traffic.load_mix("chat-1k")
    a = _drive(mix, SEED, n=traffic.STRATA)
    b = _drive(mix, 7, n=traffic.STRATA)
    for ca, cb in zip(a, b):
        assert sorted(len(p) for p, _, _ in ca) == sorted(len(p) for p, _, _ in cb)
        assert sorted(n for _, n, _ in ca) == sorted(n for _, n, _ in cb)
    assert list(traffic.strata(64, 768)) == sorted(len(p) for p, _, _ in a[0])


def test_single_clients_share_one_stratified_stream():
    """Whichever client sends it, every block of STRATA consecutive requests
    holds each prompt and each output stratum once."""
    mix = traffic.load_mix("chat-1k")
    streams = traffic.streams(mix, 1000, SEED)
    order = np.random.default_rng(3).integers(0, mix["clients"], 4 * traffic.STRATA)
    sent = [streams[c].next(None) for c in order]
    for k in range(0, len(sent), traffic.STRATA):
        block = sent[k:k + traffic.STRATA]
        assert sorted(len(p) for p, _ in block) == \
            sorted(traffic.strata(*mix["prompt_tokens"]))
        assert sorted(n for _, n in block) == \
            sorted(traffic.strata(*mix["output_tokens"]))


def test_sessions_share_exactly_system_prompt_and_history():
    mix = SESSIONS
    systems = {tuple(s) for s in traffic.streams(mix, 1000, SEED)[0].systems}
    assert len(systems) == mix["system_prompts"]
    sys_len = mix["system_tokens"]
    users = set(traffic.strata(*mix["user_tokens"]).tolist())
    rng = np.random.default_rng(1)
    turns = []
    for s in traffic.streams(mix, 1000, SEED):
        prev = None
        for _ in range(40):
            first = prev is None or s.turns_left == 0
            prompt, new = s.next(prev)
            assert tuple(prompt[:sys_len]) in systems
            if first:
                # a session opens: a system prompt and one user turn
                assert len(prompt) - sys_len in users
                turns.append(1)
            else:
                # a later turn: the previous prompt, the answer served to
                # it, then one new user turn
                head = prev[0] + prev[1]
                assert prompt[:len(head)] == head
                assert len(prompt) - len(head) in users
                turns[-1] += 1
            prev = (prompt, rng.integers(0, 1000, new).tolist())
    lo, hi = mix["turns"]
    assert set(turns[:-8]) <= set(range(lo, hi + 1)) and len(set(turns)) > 1


def test_unshared_prompts_share_no_page_aligned_prefix():
    mix = traffic.load_mix("chat-1k")
    page = mix["engine"]["page_size"]
    firsts = [tuple(p[:page]) for c in _drive(mix, SEED, n=20)
              for p, _, _ in c if len(p) >= page]
    assert len(firsts) == len(set(firsts))
