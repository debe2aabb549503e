"""Serve arithmetic on synthetic timelines: TPOT, TTFT from the due
instant, generator lateness, the measured set, the percentile, the seeded
schedule -- and the lesson of PR 22: on a staircase of tick lengths the 95th
percentile of single token gaps jumps a stair between two near-identical
timelines, while the per-request `tpot_p95_ms` barely moves."""

import math

import numpy as np
import pytest

from benchmarks import serve_arith as sa
from benchmarks import generator


def _request(due, submitted, admitted, first, gaps, want=None, status="ok"):
    done = first + sum(gaps)
    n = len(gaps) + 1
    return sa.Record(due=due, submitted=submitted, want_tokens=want or n,
                     admitted=admitted, first=first, done=done, tokens=n,
                     gaps=list(gaps), status=status)


def test_percentile_is_linear_between_order_statistics():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert sa.percentile(xs, 0) == 1.0 and sa.percentile(xs, 100) == 5.0
    assert sa.percentile(xs, 50) == 3.0
    assert sa.percentile(xs, 95) == pytest.approx(4.8)
    rng = np.random.default_rng(0)
    ys = rng.normal(size=237).tolist()
    for q in (5, 50, 95, 99):
        assert sa.percentile(ys, q) == pytest.approx(np.percentile(ys, q))
    with pytest.raises(ValueError):
        sa.percentile([], 95)


def test_tpot_ttft_queue_and_lateness_are_timed_from_due():
    # due at 10.0, submitted a tick late at 10.15, admitted 10.16, first
    # token 10.20, then three gaps
    r = _request(10.0, 10.15, 10.16, 10.20, [0.1, 0.2, 0.3])
    assert sa.tpot_ms(r) == pytest.approx(200.0)        # (0.6 / 3) s
    assert sa.ttft_ms(r) == pytest.approx(200.0)        # from DUE ...
    assert (r.first - r.submitted) * 1e3 == pytest.approx(50.0)  # not submit
    assert sa.queue_ms(r) == pytest.approx(160.0)
    assert sa.gen_late_ms(r) == pytest.approx(150.0)


def test_a_request_that_is_not_whole_misses():
    short = _request(0.0, 0.0, 0.0, 0.1, [0.1, 0.1], want=8)  # 3 of 8 tokens
    shed = sa.Record(due=0.0, submitted=0.0, want_tokens=4, status="shed")
    for r in (short, shed):
        assert not r.whole
        assert sa.tpot_ms(r) is None and math.isinf(sa.ttft_ms(r))
    good = [_request(0.0, 0.0, 0.0, 0.1, [0.1] * 3) for _ in range(30)]
    assert math.isfinite(sa.ttft_p95_ms(good + [short]))   # 1 of 31 < 5 %
    assert math.isinf(sa.ttft_p95_ms(good[:5] + [short]))  # the tail is it
    assert math.isinf(sa.tpot_p95_ms(good[:5] + [shed]))


def test_measured_set_is_every_request_due_inside_the_window():
    recs = [_request(t, t, t, t + 0.1, [0.1]) for t in
            (-0.5, 0.0, 3.0, 9.999, 10.0, 12.0)]
    got = sa.measured_set(recs, t0=0.0, window_s=10.0)
    assert [r.due for r in got] == [0.0, 3.0, 9.999]


def _staircase(ticks_with_two_admissions):
    """240 requests of 41 tokens over ticks of 150 ms + 15 ms a prefill
    admitted in the tick.  470 of the 9600 ticks admit one request; the
    argument is how many of each request's 40 ticks admit two."""
    base, prefill = 0.150, 0.015
    recs = []
    for k in range(240):
        n_one = 2 if k % 24 else 1           # ticks that admit one request
        n_two = ticks_with_two_admissions(k)
        gaps = ([base + 2 * prefill] * n_two + [base + prefill] * n_one
                + [base] * (40 - n_one - n_two))
        recs.append(_request(float(k), float(k), float(k), k + 0.2, gaps))
    return recs


def test_staircase_itl_p95_jumps_a_stair_while_tpot_p95_barely_moves():
    # timeline A: 4.90 % of all token gaps sit above the ground stair;
    # timeline B: in 26 more ticks of 9600 a request is admitted -> 5.17 %
    a = _staircase(lambda k: 0)
    b = _staircase(lambda k: 1 if k < 26 else 0)
    itl_a, itl_b = sa.itl_p95_ms(a), sa.itl_p95_ms(b)
    tpot_a, tpot_b = sa.tpot_p95_ms(a), sa.tpot_p95_ms(b)
    # the gap percentile sits on a stair and moves by a whole stair ...
    assert itl_a == pytest.approx(150.0)
    assert itl_b == pytest.approx(165.0)
    assert (itl_b - itl_a) / itl_a == pytest.approx(0.10)
    # ... the per-request mean gap is smooth: under 1 %
    assert abs(tpot_b - tpot_a) / tpot_a < 0.01


# -- the seeded schedule -----------------------------------------------------

MIX = {"arrival": {"process": "poisson", "rate_rps": 6.0},
       "prompt_len": {"dist": "log_uniform", "lo": 64, "hi": 768},
       "output_len": {"dist": "log_uniform", "lo": 16, "hi": 128},
       "ramp_s": 5.0, "tail_s": 10.0, "multiset_seed": 24}


def _by_phase(arrivals, phase):
    return [a for a in arrivals if a.phase == phase]


def test_schedule_same_seed_same_inputs():
    a = generator.schedule(MIX, 2**31 + 5, 20.0, 50304)
    b = generator.schedule(MIX, 2**31 + 5, 20.0, 50304)
    assert a == b


def test_schedule_phases_counts_and_bounds():
    arr = generator.schedule(MIX, 7, 20.0, 50304)
    assert [len(_by_phase(arr, p)) for p in ("ramp", "window", "tail")] == [
        30, 120, 60]
    assert [a.due_s for a in arr] == sorted(a.due_s for a in arr)
    assert _by_phase(arr, "window")[0].due_s == 0.0
    assert all(-5.0 <= a.due_s < 0.0 for a in _by_phase(arr, "ramp"))
    assert all(0.0 <= a.due_s < 20.0 for a in _by_phase(arr, "window"))
    assert all(20.0 <= a.due_s < 30.0 for a in _by_phase(arr, "tail"))
    for a in arr:
        assert 64 <= len(a.prompt) <= 768 and 16 <= a.max_new_tokens <= 128
        assert 0 <= min(a.prompt) and max(a.prompt) < 50304


def test_every_seed_offers_the_same_work_in_another_order():
    """The seed permutes a fixed multiset of gaps and lengths: two seeds
    differ in order and token values, never in the amount of work."""
    a = _by_phase(generator.schedule(MIX, 1, 20.0, 50304), "window")
    b = _by_phase(generator.schedule(MIX, 2**31 + 9, 20.0, 50304), "window")
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert sorted(len(x.prompt) for x in a) == sorted(
        len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == sorted(
        x.max_new_tokens for x in b)
    gaps = lambda xs: sorted(np.round(np.diff([x.due_s for x in xs]), 9))
    # all gaps but the phase's last (which closes the window) are shared
    assert len(set(gaps(a)) & set(gaps(b))) >= len(a) - 3
    assert a[0].prompt != b[0].prompt


def test_shuffle_block_keeps_the_trace_and_reorders_it_locally():
    """With `shuffle_block` the seed shuffles inside consecutive blocks of
    arrivals only: every seed replays the same trace -- the same bursts and
    lulls, the same lengths in the same stretch -- in another local order."""
    local = dict(MIX, shuffle_block=4)
    a = _by_phase(generator.schedule(local, 1, 20.0, 50304), "window")
    b = _by_phase(generator.schedule(local, 2**31 + 9, 20.0, 50304), "window")
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    for i in range(0, len(a), 4):
        assert sorted(len(x.prompt) for x in a[i:i + 4]) == sorted(
            len(x.prompt) for x in b[i:i + 4])
        assert sorted(x.max_new_tokens for x in a[i:i + 4]) == sorted(
            x.max_new_tokens for x in b[i:i + 4])
    # block boundaries fall at the same instants whatever the seed
    assert [x.due_s for x in a[::4]] == pytest.approx(
        [x.due_s for x in b[::4]])


def test_other_processes_and_shared_prefixes_need_no_code():
    bursty = dict(MIX, arrival={"process": "gamma", "rate_rps": 6.0,
                                "cv": 3.0})
    arr = _by_phase(generator.schedule(bursty, 3, 20.0, 1000), "window")
    g = np.diff([a.due_s for a in arr])
    assert len(arr) == 120 and g.std() / g.mean() > 1.5   # burstier
    shared = dict(MIX, shared_prefix={"pool": 4, "len": 32, "zipf_a": 1.2},
                  prompt_len={"dist": "choice", "values": [8, 16]})
    arr = _by_phase(generator.schedule(shared, 3, 20.0, 1000), "window")
    prefixes = {tuple(a.prompt[:32]) for a in arr}
    assert len(prefixes) <= 4
    assert {len(a.prompt) for a in arr} == {40, 48}
    with pytest.raises(ValueError):
        generator.schedule(dict(MIX, arrival={"process": "nope",
                                            "rate_rps": 1.0}), 0, 5.0, 10)
