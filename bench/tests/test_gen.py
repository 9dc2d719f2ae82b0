"""The traffic generator repeats exactly for one seed, and every seed
offers the same work in another order."""
import numpy as np

from bench import gen

MIX = {"arrivals": {"kind": "poisson", "rate_per_s": 3.0},
       "prompt": {"dist": "lognormal", "median": 1024, "sigma": 0.6,
                  "min": 128, "max": 2048},
       "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                  "min": 9, "max": 513, "round_up_to": 8, "plus": 1}}
CLOSED = dict(MIX, arrivals={"kind": "closed", "clients": 8,
                             "requests_per_client": 4})
BIG = 2 ** 40 + 12345          # the driver's seeds pass 32 bits
SEGMENTS = (5.0, 51.0, 20.0)


def _tuples(items):
    return [(i.rid, i.prompt_len, i.max_tokens, i.due, i.client)
            for i in items]


def _in(items, t0, t1):
    return [i for i in items if t0 <= i.due < t1]


def test_same_seed_same_schedule_and_tokens():
    a = gen.schedule(MIX, BIG, SEGMENTS)
    b = gen.schedule(MIX, BIG, SEGMENTS)
    assert _tuples(a) == _tuples(b)
    ta, tb = gen.token_ids(BIG, a, 49155), gen.token_ids(BIG, b, 49155)
    assert all(np.array_equal(x, y) for x, y in zip(ta, tb))


def test_seeds_share_the_window_in_another_order():
    a = gen.schedule(MIX, BIG, SEGMENTS)
    b = gen.schedule(MIX, BIG + 1, SEGMENTS)
    assert _tuples(a) != _tuples(b)
    for t0, t1 in ((0, 5), (5, 56), (56, 76)):
        wa, wb = _in(a, t0, t1), _in(b, t0, t1)
        # each segment: floor(rate * span) requests, the same sizes
        assert len(wa) == len(wb) == int(3.0 * (t1 - t0))
        assert sorted(i.prompt_len for i in wa) == \
            sorted(i.prompt_len for i in wb)
        assert sorted(i.max_tokens for i in wa) == \
            sorted(i.max_tokens for i in wb)
        ga = np.sort(np.diff([i.due for i in wa]))
        gb = np.sort(np.diff([i.due for i in wb]))
        # the same gaps, less the one each order leaves at the end
        assert len(ga) == len(gb) == len(wa) - 1
        assert wa[0].due == wb[0].due == t0
    assert len(a) == 15 + 153 + 60


def test_lengths_respect_bounds_and_rounding():
    items = gen.schedule(MIX, 3, (0.0, 600.0))
    out = np.array([i.max_tokens for i in items])
    pl = np.array([i.prompt_len for i in items])
    assert out.min() >= 9 and out.max() <= 513
    assert np.all((out - 1) % 8 == 0)
    assert pl.min() >= 128 and pl.max() <= 2048
    assert 900 <= np.median(pl) <= 1150
    assert len(items) == 1800


def test_closed_loop_deals_requests_to_clients():
    items = gen.schedule(CLOSED, 5, ())
    assert len(items) == 32 and all(i.due is None for i in items)
    assert np.bincount([i.client for i in items]).tolist() == [4] * 8


def test_closed_loop_first_requests_keep_a_share():
    a = gen.schedule(CLOSED, BIG, ())
    first = np.array([i.max_tokens for i in a[:8]])
    rest = np.array([i.max_tokens for i in a[8:]])
    assert [i.client for i in a[:8]] == list(range(8))
    assert first.min() >= 9 and np.all((first - 1) % 8 == 0)
    assert len(set(first.tolist())) > 4
    # shares (i + 0.5) / 8 average a half: over seeds, the first
    # requests hold about half the tokens of as many later ones
    ratio = [np.mean([i.max_tokens for i in s[:8]]) /
             np.mean([i.max_tokens for i in s[8:]])
             for s in (gen.schedule(CLOSED, BIG + k, ()) for k in range(200))]
    assert 0.4 < np.mean(ratio) < 0.6
    # later requests keep the mix's stratified lengths
    assert set(rest.tolist()) <= set(gen.lengths(CLOSED["output"], 32))


def test_jax_seed_fits_31_bits_and_differs():
    s = [gen.jax_seed(gen.seed_streams(x)[2]) for x in (BIG, BIG + 1, 0)]
    assert all(0 <= v < 2 ** 31 for v in s) and len(set(s)) == 3
