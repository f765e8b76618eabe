"""The families' analytic operation and byte counts against hand counts."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.run import Counts, load_module  # noqa: E402

GALAXY = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 2,
          "intermediate_size": 32, "vocab_size": 10, "dtype": "float32"}


def _family(name):
    return load_module(os.path.join(ROOT, "perfbench", "families", name + ".py"))


def test_galaxy_counts_by_hand():
    f = _family("galaxy_postln")
    # per layer: q, k, v, o (8x8 each) and 8x32, 32x8; two flops a MAC
    assert f.linear_flops_per_token(GALAXY) == 2 * 2 * (4 * 64 + 2 * 256)
    # per layer: q.k and p.v over 2 heads of 4 at position 5 (6 keys)
    assert f.attention_flops(GALAXY, 5) == 2 * (2 * 2 * 4 * 6) * 2
    assert f.unembed_flops(GALAXY) == 2 * 8 * 10
    # four 8x8, 8x32 + 32x8, two layer norms (scale, bias); tied table
    assert f.weight_bytes(GALAXY) == (2 * (256 + 512 + 32) + 80) * 4
    assert f.kv_bytes_per_position(GALAXY) == 2 * 2 * 8 * 4


@pytest.mark.parametrize("family,conf", [("galaxy_postln", GALAXY)])
def test_decode_bytes_count_live_positions_only(family, conf):
    """A decode call's bytes are the weights once and the K/V of each live
    slot up to its position: the least any implementation moves, so the
    roofline share it gives cannot pass 100%.  They never reach the whole
    block row a gather reads."""
    f = _family(family)
    counts = Counts(f, conf)
    max_len, slots = 64, 4
    row = f.weight_bytes(conf) + slots * max_len * f.kv_bytes_per_position(conf)
    prev = None
    for live in range(1, max_len):
        pos = np.full(slots, live - 1)
        call = counts.decode(pos)
        assert call["bytes"] == f.weight_bytes(conf) + slots * live * \
            f.kv_bytes_per_position(conf)
        assert call["bytes"] <= row
        assert prev is None or call["bytes"] > prev
        prev = call["bytes"]
    assert counts.decode(np.array([], int))["bytes"] == f.weight_bytes(conf)


def test_prefill_counts_one_logits_row():
    f = _family("galaxy_postln")
    call = Counts(f, GALAXY).prefill(3, 4)
    assert call["tokens"] == 4
    assert call["flops"] == (4 * f.linear_flops_per_token(GALAXY)
                             + sum(f.attention_flops(GALAXY, p)
                                   for p in range(3, 7))
                             + f.unembed_flops(GALAXY))
