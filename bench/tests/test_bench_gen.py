"""Traffic generation: deterministic in the seed, and the same work for
every seed."""
import numpy as np

import bench_tiny  # noqa: F401  (puts the repo on the path)
from bench.lib import gen

SPEC = {"median": 96, "sigma": 0.7, "lo": 16, "hi": 512}
BIG = 2 ** 31 + 12345


def test_same_seed_same_traffic():
    a, b = gen.rng(BIG, 1), gen.rng(BIG, 1)
    np.testing.assert_array_equal(gen.lognormal(200, SPEC, a),
                                  gen.lognormal(200, SPEC, b))
    for x, y in zip(gen.prompts([3, 9], 100, a), gen.prompts([3, 9], 100, b)):
        np.testing.assert_array_equal(x, y)


def test_seeds_permute_one_multiset():
    a = gen.lognormal(300, SPEC, gen.rng(1, 1))
    b = gen.lognormal(300, SPEC, gen.rng(2, 1))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))


def test_lognormal_shape():
    x = gen.lognormal(1001, SPEC, gen.rng(5, 1))
    assert x.min() >= 16 and x.max() <= 512
    assert abs(np.median(x) - 96) <= 1


def test_bucket_rule():
    assert [gen.bucket(n, 4095) for n in (1, 2, 3, 16, 17, 300)] == \
        [1, 2, 4, 16, 32, 512]
    assert gen.bucket(5000, 4095) == 4095


def test_schedule_is_one_for_every_seed():
    a = gen.lognormal(300, SPEC, gen.schedule_rng(1))
    b = gen.lognormal(300, SPEC, gen.schedule_rng(1))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, np.sort(a))
