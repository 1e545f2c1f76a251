"""The traffic generator and the pool size, checked by hand on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests/test_traffic.py
"""
from __future__ import annotations

import json
import math
import sys
from collections import Counter

import numpy as np
import pytest

import tiny

sys.path[:0] = [str(tiny.BENCH), str(tiny.BENCH.parent / "src")]

from harness import build, traffic  # noqa: E402

MIXES = {name: json.loads((tiny.BENCH / "traffic" / f"{name}.json")
                          .read_text())
         for name in ("decode-batch", "chat-rate")}


def test_sigma_spans_the_range_with_two_sigma_each_side():
    d = {"median": 256, "min": 128, "max": 512}
    assert traffic.sigma(d) == pytest.approx(math.log(2) / 2)
    lengths = traffic.quantile_lengths(d, 1000)
    # 2.3% of a lognormal lies beyond two sigma on each side
    assert np.mean(lengths == 128) == pytest.approx(0.023, abs=0.002)
    assert np.mean(lengths == 512) == pytest.approx(0.023, abs=0.002)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_every_seed_draws_the_same_lengths(mix):
    def block(seed):
        gen = traffic.Generator(MIXES[mix], seed, 1000)
        reqs = [gen.next() for _ in range(MIXES[mix]["block"])]
        return reqs, Counter((len(r.prompt), r.max_new) for r in reqs)

    a, lens_a = block(5)
    b, lens_b = block(2**31 + 17)
    assert Counter(len(r.prompt) for r in a) == Counter(
        len(r.prompt) for r in b)
    assert Counter(r.max_new for r in a) == Counter(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_in_flight_is_the_same_work_for_every_seed_and_length_biased():
    mix = MIXES["decode-batch"]
    n = mix["clients"]

    def work(seed):
        reqs = traffic.Generator(mix, seed, 1000).in_flight(n)
        return reqs, (sum(len(r.prompt) for r in reqs),
                      sum(r.max_new for r in reqs))

    a, total_a = work(3)
    b, total_b = work(9_000_000_001)
    assert total_a == total_b
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.max_new for r in a] != [r.max_new for r in b]
    # each request's whole output lies in the mix's range, its remainder
    # at least one token
    out = mix["output_tokens"]
    plen = mix["prompt_tokens"]
    for r in a:
        assert 1 <= r.max_new <= out["max"]
        assert plen["min"] <= len(r.prompt) <= plen["max"] + out["max"] - 1
    # length-biased: a client is on a request for as long as its output
    # takes, so the whole outputs in flight average E[L^2] / E[L]
    lengths = traffic.quantile_lengths(out, mix["block"]).astype(float)
    whole = (sum(len(r.prompt) + r.max_new for r in a)
             - traffic.quantile_lengths(plen, n).sum()) / n
    assert whole == pytest.approx(np.mean(lengths**2) / np.mean(lengths),
                                  rel=0.01)
    assert whole > np.mean(lengths) * 1.05


class _Model:
    n_layers, n_kv_heads, head_dim, dtype = 24, 8, 128, "bfloat16"


def test_pool_fits_twice_beside_the_headroom():
    page = build.kv_page_bytes(_Model, 128)
    assert page == 24 * 2 * 128 * 8 * 128 * 2
    mix = {"page_size": 128, "slots": 64}
    free = 13 * 10**9
    n = build.pool_pages(_Model, mix, 1536, free)
    assert (n - 1) % 16 == 0
    assert 2 * (n - 1) * page <= free - build.STEP_HEADROOM_BYTES
    assert 2 * (n - 1 + 16) * page > free - build.STEP_HEADROOM_BYTES
    # where every slot's worst case fits, that is the pool
    assert build.pool_pages(_Model, {"page_size": 128, "slots": 2}, 1536,
                            free) == 1 + 2 * 12
