"""The traffic generator: a seed fixes the arrivals, and seeds differ only
in the order of one fixed set of sizes and gaps."""
from __future__ import annotations

import json

import numpy as np
import pytest

import chipbench_smoke  # noqa: F401  (puts the repo root on sys.path)
from chipbench import traffic
from chipbench.spec import HERE

MIXES = sorted(p.stem for p in (HERE / "mixes").glob("*.json"))
BIG_SEED = 2**31 + 12345


def mix(name):
    return json.loads((HERE / "mixes" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_schedule_repeats_per_seed_and_differs_across_seeds(name):
    m = mix(name)
    a = traffic.schedule(m, BIG_SEED, 45)
    b = traffic.schedule(m, BIG_SEED, 45)
    c = traffic.schedule(m, BIG_SEED + 1, 45)
    assert a == b
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_one_set_of_sizes(name):
    m = mix(name)
    a = traffic.schedule(m, 3, 45)
    b = traffic.schedule(m, 4, 45)
    for seg in (traffic.WARM, traffic.WINDOW, traffic.TAIL):
        sa = [x for x in a if x.segment == seg]
        sb = [x for x in b if x.segment == seg]
        assert len(sa) == len(sb)
        assert sorted(x.prompt_len for x in sa) == sorted(x.prompt_len for x in sb)
        assert sorted(x.max_new for x in sa) == sorted(x.max_new for x in sb)
    allowed = set(m["prompt"]["snap"])
    assert all(x.prompt_len in allowed for x in a)
    assert all(m["output"]["min"] <= x.max_new <= m["output"]["max"] for x in a)


def test_arrivals_fall_in_their_segments_in_order():
    m = mix("burst-a5")
    arr = traffic.schedule(m, BIG_SEED, 45)
    dues = [x.due for x in arr]
    assert dues == sorted(dues)
    for x in arr:
        lo, hi = {traffic.WARM: (-m["warm_in_s"], 0), traffic.WINDOW: (0, 45),
                  traffic.TAIL: (45, 45 + m["grace_s"])}[x.segment]
        assert lo <= x.due < hi


def test_bursty_intensity_inverts_and_bursts():
    lam = traffic.Intensity({"process": "bursty", "period_s": 9, "burst_s": 2,
                             "burst_rate": 26.0, "base_rate": 10.0})
    for t in (-7.5, -1.0, 0.0, 1.0, 2.5, 8.9, 30.0):
        assert lam.inv(lam.cum(t)) == pytest.approx(t, abs=1e-9)
    assert lam.cum(9.0) - lam.cum(0.0) == pytest.approx(26 * 2 + 10 * 7)
    m = dict(mix("burst-a5"), warm_in_s=0, grace_s=0)
    a = m["arrivals"]
    horizon = 450  # fifty periods: enough arrivals that the densities show
    arr = traffic.schedule(m, 5, horizon)
    in_burst = sum(1 for x in arr if x.due % a["period_s"] < a["burst_s"])
    periods = horizon / a["period_s"]
    expected = periods * a["burst_s"] * a["burst_rate"]
    # Poisson counts: within four standard deviations
    assert abs(in_burst - expected) < 4 * expected ** 0.5
    burst_density = in_burst / (periods * a["burst_s"])
    base_density = (len(arr) - in_burst) / (horizon - periods * a["burst_s"])
    assert burst_density > 1.5 * base_density


def test_poisson_window_count_is_fixed():
    m = mix("chat-f32")
    rate = m["arrivals"]["rate"]
    for seed in (1, 2, BIG_SEED):
        win = [x for x in traffic.schedule(m, seed, 45) if x.segment == traffic.WINDOW]
        assert len(win) == round(rate * 45)


def test_length_quantiles_follow_the_lognormal():
    x = traffic.length_quantiles({"median": 100, "sigma": 0.5}, 1001)
    assert np.median(x) == 100
    assert x.min() >= 1 and np.all(np.diff(x) >= 0)


def test_backlog_has_no_due_times():
    backlog = dict(mix("chat-f32"), arrivals={"process": "backlog", "depth": 4,
                                              "requests": 128, "block": 32})
    arr = traffic.schedule(backlog, BIG_SEED, 45)
    assert len(arr) == 128 and all(x.due is None for x in arr)
    assert sorted(x.prompt_len for x in arr[:32]) == sorted(x.prompt_len for x in arr[32:64])


def test_prompt_tokens_repeat_and_stay_in_vocab():
    a = traffic.schedule(mix("chat-f32"), BIG_SEED, 45)[0]
    p1 = traffic.prompt_tokens(BIG_SEED, a, 1000)
    assert np.array_equal(p1, traffic.prompt_tokens(BIG_SEED, a, 1000))
    assert len(p1) == a.prompt_len and p1.min() >= 0 and p1.max() < 1000
