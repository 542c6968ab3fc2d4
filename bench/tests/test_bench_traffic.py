"""The traffic generator: seeded, stratified, and the same work per seed."""
import numpy as np

from bench.traffic import gen

CHAT = {"arrivals": "poisson",
        "prompt": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                   "min": 32, "max": 768},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 8, "max": 192},
        "size_seed": 0}


def _key(reqs):
    return [(r.rid, r.due, r.max_new, r.in_window, r.prompt.tobytes())
            for r in reqs]


def test_same_seed_same_requests():
    a = gen.generate(CHAT, 2.0, 2**31 + 5, 30, 1000, 960)
    b = gen.generate(CHAT, 2.0, 2**31 + 5, 30, 1000, 960)
    assert _key(a) == _key(b)


def test_other_seed_other_requests_same_work():
    """A seed draws the content; the schedule of sizes is the mix's."""
    a = gen.generate(CHAT, 2.0, 11, 30, 1000, 960)
    b = gen.generate(CHAT, 2.0, 12, 30, 1000, 960)
    assert _key(a) != _key(b)
    assert [(r.due, len(r.prompt), r.max_new, r.in_window) for r in a] == \
        [(r.due, len(r.prompt), r.max_new, r.in_window) for r in b]
    assert sum(r.in_window for r in a) == 60
    assert max(r.due for r in a if r.in_window) < 30 <= min(
        r.due for r in a if not r.in_window)
    pa = gen.preload(CHAT, 24, 11, 1000, 16)
    pb = gen.preload(CHAT, 24, 12, 1000, 16)
    assert [(len(r.prompt), r.max_new) for r in pa] == \
        [(len(r.prompt), r.max_new) for r in pb]
    assert [r.prompt.tobytes() for r in pa] != [r.prompt.tobytes()
                                               for r in pb]


def test_preload_covers_every_residue():
    held = gen.preload(CHAT, 24, 3, 1000, 16)
    assert {len(r.prompt) % 16 for r in held} == set(range(16))
    assert all(not r.in_window and r.due < 0 for r in held)
    assert all(1 <= r.max_new <= 192 for r in held)
    assert all(32 <= len(r.prompt) <= 768 for r in held)


def test_lognormal_median_and_clips_over_10k_draws():
    p = gen.lengths(CHAT["prompt"], 10_000)
    o = gen.lengths(CHAT["output"], 10_000)
    assert np.median(p) == 512 and np.median(o) == 128
    assert p.min() == 32 and p.max() == 768
    assert o.min() == 8 and o.max() == 192
    # clipped mass: P(prompt > 768) = P(z > ln 1.5) = 0.334
    assert abs((p == 768).mean() - 0.334) < 0.01
    u = gen.lengths({"dist": "uniform", "min": 16, "max": 64}, 10_000)
    assert u.min() == 16 and u.max() == 64
    assert abs(np.median(u) - 40) <= 1


def test_rate_and_tokens():
    reqs = gen.generate(CHAT, 2.0, 3, 30, 500, 960)
    w = [r for r in reqs if r.in_window]
    assert len(w) == 60
    gaps = np.diff([0.0] + [r.due for r in reqs])
    assert abs(gaps[:60].mean() - 0.5) < 0.01
    assert all(0 <= r.prompt.min() and r.prompt.max() < 500 for r in reqs)


def test_mix_longer_than_context_is_refused():
    import pytest
    with pytest.raises(ValueError, match="serves 512"):
        gen.generate(CHAT, 2.0, 3, 30, 500, 512)
