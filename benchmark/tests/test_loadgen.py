"""The generator's schedule: reproducible from the seed, and the same
offered work for every seed."""
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, loadgen  # noqa: E402

TRAFFIC = harness.load_json(os.path.join(
    REPO, 'benchmark', 'traffic', 'http_open_0p8knee.json'))


def test_same_seed_same_schedule():
    a = loadgen.schedule(TRAFFIC, 2 ** 31 + 5, 20.0)
    b = loadgen.schedule(TRAFFIC, 2 ** 31 + 5, 20.0)
    assert a == b


def test_every_seed_offers_the_same_work_in_another_order():
    a = loadgen.schedule(TRAFFIC, 1, 20.0)
    b = loadgen.schedule(TRAFFIC, 2, 20.0)
    assert a != b
    assert sorted(r for _, r in a) == sorted(r for _, r in b)
    gaps = lambda p: sorted(round(y[0] - x[0], 9)          # noqa: E731
                            for x, y in zip(p, p[1:]))
    # the gaps are one multiset (but for the first, which starts at 0)
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 0.5
    assert a[0][0] == 0.0 and a[-1][0] < 20.0


def test_rate_and_mix():
    plan = loadgen.schedule(TRAFFIC, 3, 30.0)
    rows = sum(r for _, r in plan)
    assert abs(rows / 30.0 - TRAFFIC['rate_rows_s']) \
        < 0.05 * TRAFFIC['rate_rows_s']
    ones = sum(1 for _, r in plan if r == 1) / len(plan)
    assert abs(ones - 0.60) < 0.02
    assert max(r for _, r in plan) == 32


def test_sample_holds_the_largest_request():
    plan = loadgen.schedule(TRAFFIC, 4, 20.0)
    keep = loadgen.sample_ids(plan, 4, 12)
    assert max(plan[i][1] for i in keep) == max(r for _, r in plan)
    assert keep == loadgen.sample_ids(plan, 4, 12)


def test_bodies_are_the_images_the_driver_regenerates():
    import io
    import numpy as np
    tr = dict(TRAFFIC, body_pool_rows=40)
    made = loadgen.bodies(tr, 9, (3, 8, 8))
    pool = loadgen.body_pool(tr, 9, (3, 8, 8))
    got = np.load(io.BytesIO(made[(4, 2)]))
    assert np.array_equal(got, loadgen.body_rows(pool, 4, 2))
    assert got.dtype == np.float32 and got.shape == (4, 3, 8, 8)
