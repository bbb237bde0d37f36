"""The closed-loop batch generator (CPU only, no JAX)."""

import json
from pathlib import Path

import numpy as np

from bench.traffic import batches

TRAFFIC = Path(__file__).parents[1] / "traffic"


def test_stratified_lengths_follow_the_law():
    law = {"law": "lognormal", "median": 512, "sigma": 0.6, "min": 128,
           "max": 1536}
    got = batches.stratified_lengths(law, 16)
    assert got == sorted(got) and len(got) == 16
    assert got[0] >= 128 and got[-1] <= 1536
    assert abs((got[7] * got[8]) ** 0.5 - 512) < 1   # lognormal: geometric
    uni = batches.stratified_lengths({"law": "uniform", "min": 0,
                                      "max": 80}, 4)
    assert uni == [10, 30, 50, 70]


def test_zipf_counts_sum_to_the_batch():
    assert batches.zipf_counts(4, 1.0, 16) == [8, 4, 2, 2]
    assert sum(batches.zipf_counts(3, 1.2, 7)) == 7


def test_every_seed_serves_the_same_sizes_in_another_order():
    mix = json.loads((TRAFFIC / "chat.json").read_text())
    a = batches.make(mix, 151936, 7)
    b = batches.make(mix, 151936, 2**33 + 1)     # wider than 32 bits
    la, lb = [len(p) for p in a.batch()], [len(p) for p in b.batch()]
    assert sorted(la) == sorted(lb) and la != lb
    again = batches.make(mix, 151936, 7).batch()
    assert all(np.array_equal(x, y) for x, y in zip(a.batch(), again)) is False
    first = batches.make(mix, 151936, 7)
    assert all(np.array_equal(x, y)
               for x, y in zip(first.batch(), batches.make(mix, 151936,
                                                           7).batch()))
    toks = np.concatenate(a.batch())
    assert toks.min() >= 1 and toks.max() < 151936


def test_shared_prefix_batches_reuse_the_system_prompts():
    mix = json.loads((TRAFFIC / "shared-prefix.json").read_text())
    g = batches.make(mix, 151936, 3)
    warm = g.warmup()
    assert len(warm) == 4 and all(len(p) == 1536 for p in warm)
    heads = [p[:1536].tobytes() for p in g.batch()]
    counts = sorted((heads.count(w.tobytes()) for w in warm), reverse=True)
    assert counts == [8, 4, 2, 2]
    assert all(32 <= len(p) - 1536 <= 256 for p in g.batch())
