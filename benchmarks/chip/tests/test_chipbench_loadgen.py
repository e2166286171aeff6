"""Seeded generators: the same seed sends the same requests; every seed
sends the same work in another order."""
import numpy as np
import pytest

from chipbench_testlib import harness

BIG = 2**31 + 12345


def gens(kind, mix, seed, seconds=20.0):
    return harness().module(f"loadgen/{kind}.py").Generator(
        mix, seed, seconds, 1000)


CLOSED = {"clients": 3, "sizes": 4,
          "prompt": {"dist": "loguniform", "lo": 100, "hi": 1000},
          "output": {"fixed": 16}}
OPEN = {"rate_per_s": 3.0, "gamma_shape": 0.25,
        "prompt": {"dist": "lognormal", "median": 64, "sigma": 1.0,
                   "lo": 8, "hi": 256},
        "output": {"dist": "lognormal", "median": 16, "sigma": 1.0,
                   "lo": 4, "hi": 64}}


def closed_stream(seed, n=12):
    g = gens("closed", CLOSED, seed)
    out = g.start()
    while len(out) < n:
        out += g.done(0, 1.0)
    return out


def same(a, b):
    return len(a) == len(b) and all(
        x[0] == y[0] and x[1] == y[1] and x[3] == y[3]
        and np.array_equal(x[2], y[2]) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**40 + 3])
def test_closed_repeats_exactly(seed):
    assert same(closed_stream(seed), closed_stream(seed))


@pytest.mark.parametrize("seed", [0, BIG])
def test_open_repeats_exactly(seed):
    assert same(gens("open", OPEN, seed).start(),
                gens("open", OPEN, seed).start())


def test_closed_rounds_hold_every_size_once():
    s = closed_stream(BIG, n=8)
    lens = [len(p) for _, _, p, _ in s]
    assert sorted(lens) == sorted(2 * lens[:4]) and len(set(lens)) == 4


def test_every_seed_sends_the_same_work():
    """The seed draws tokens; lengths, order and arrival times are the
    same for every seed, so seeds spread no more than runs of one."""
    a, b = closed_stream(1, n=8), closed_stream(BIG, n=8)
    assert [len(p) for _, _, p, _ in a] == [len(p) for _, _, p, _ in b]
    assert not all(np.array_equal(x[2], y[2]) for x, y in zip(a, b))
    a, b = gens("open", OPEN, 1).start(), gens("open", OPEN, BIG).start()
    assert len(a) == len(b) == 60
    assert [(t, len(p), o) for t, _, p, o in a] == \
        [(t, len(p), o) for t, _, p, o in b]
    assert not all(np.array_equal(x[2], y[2]) for x, y in zip(a, b))
    assert max(t for t, *_ in a) < 20.0 and a[0][0] == 0.0


def test_open_gaps_are_bursty():
    d = np.diff([t for t, *_ in gens("open", OPEN, 3, 100.0).start()])
    assert d.std() / d.mean() > 1.3     # Gamma shape 0.25: CV near 2


def test_quantile_sizes():
    q = harness().module("loadgen/common.py").quantile_sizes
    assert q({"fixed": 5}, 3) == [5, 5, 5]
    v = q({"dist": "loguniform", "lo": 100, "hi": 10000}, 2)
    assert v == [round(100 * 10 ** 0.5), round(100 * 10 ** 1.5)]
    v = q({"dist": "lognormal", "median": 50, "sigma": 2.0, "lo": 10,
           "hi": 60}, 3)
    assert v == [10, 50, 60]
