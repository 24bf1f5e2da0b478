"""Traffic generators: the same seed gives the same requests, and every
seed gets the same multiset of sizes in another order."""
import numpy as np

from bench import load
from bench.generators import closed


def test_rounds_deterministic_per_seed():
    mix = load.load_mix("decode")
    a = closed.rounds(mix, 2 ** 33 + 5, 50272, 2)
    b = closed.rounds(mix, 2 ** 33 + 5, 50272, 2)
    assert len(a) == 2 * mix["clients"]
    assert all(np.array_equal(p, q) and o == r
               for (p, o), (q, r) in zip(a, b))


def test_seeds_share_sizes_not_order():
    mix = load.load_mix("decode")
    a = closed.rounds(mix, 1, 50272, 3)
    b = closed.rounds(mix, 2, 50272, 3)
    c = mix["clients"]
    for r in range(3):
        ra, rb = a[r * c:(r + 1) * c], b[r * c:(r + 1) * c]
        assert sorted(len(p) for p, _ in ra) == sorted(len(p) for p, _ in rb)
        assert sorted(o for _, o in ra) == sorted(o for _, o in rb)
    assert [len(p) for p, _ in a] != [len(p) for p, _ in b]
    assert not np.array_equal(a[0][0][:16], b[0][0][:16])


def test_lengths_within_the_mix():
    for name in ("decode", "longctx", "mini-closed"):
        mix = load.load_mix(name)
        for key in ("prompt", "output"):
            q = load.quantiles(mix[key], 257)
            assert q.min() >= mix[key]["min"] and q.max() <= mix[key]["max"]
            assert abs(np.median(q) - mix[key]["median"]) <= 1
        assert load.max_context(mix) <= 2048     # OPT's positions


def test_large_seed():
    r = load.rng(2 ** 31 + 12345, 1)
    assert 0 <= r.integers(0, 10) < 10
