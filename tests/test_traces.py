"""Property tests for the availability-trace combinators (DESIGN.md §16).

Pins the contracts the spot-churn subsystem leans on: determinism
(same-seed `random_spikes` traces are pointwise identical), range (every
composition stays inside (0, 1], including the 1e-6 floor interacting with
stacked `preemption(level=1e-3)` windows), and the half-open boundary
convention — the instant an event starts it is in effect (`t == at`,
`t == start`), the instant it ends it is over (`t == restore`), and `ramp`
reaches its floor exactly at `t == start + duration`.
"""

import math

from hypothesis import given, settings
from hypothesis import strategies as st

from repro.het import traces

seeds = st.integers(min_value=0, max_value=2**31 - 1)
times = st.floats(min_value=0.0, max_value=500.0, allow_nan=False,
                  allow_infinity=False)
levels = st.floats(min_value=1e-3, max_value=1.0, allow_nan=False,
                   allow_infinity=False)


class TestDeterminism:
    @given(seed=seeds, data=st.data())
    @settings(max_examples=50, deadline=None)
    def test_same_seed_random_spikes_pointwise_identical(self, seed, data):
        a = traces.random_spikes(seed, horizon=300.0)
        b = traces.random_spikes(seed, horizon=300.0)
        for _ in range(20):
            t = data.draw(times)
            assert a(t) == b(t)

    def test_different_seeds_differ_somewhere(self):
        a = traces.random_spikes(0, horizon=300.0, rate_per_100s=10.0)
        b = traces.random_spikes(1, horizon=300.0, rate_per_100s=10.0)
        grid = [i * 0.5 for i in range(600)]
        assert any(a(t) != b(t) for t in grid)


class TestRange:
    @given(seed=seeds, level=levels, t=times)
    @settings(max_examples=50, deadline=None)
    def test_compose_stays_in_unit_interval(self, seed, level, t):
        tr = traces.compose(
            traces.random_spikes(seed, horizon=500.0, level=level),
            traces.step_interference(10.0, 50.0, level),
            traces.periodic_interference(30.0, 0.4, level),
            traces.ramp(100.0, 50.0, level),
        )
        v = tr(t)
        assert 0.0 < v <= 1.0

    @given(t=times)
    @settings(max_examples=50, deadline=None)
    def test_stacked_preemptions_hit_the_floor_not_zero(self, t):
        # two overlapping preemptions at level=1e-3 multiply to exactly
        # 1e-6 (the clamp boundary); a third must clamp, never go below
        tr = traces.compose(
            traces.preemption(0.0, level=1e-3),
            traces.preemption(0.0, level=1e-3),
            traces.preemption(0.0, level=1e-3),
        )
        assert tr(t) == 1e-6

    def test_two_preemptions_sit_exactly_on_the_clamp(self):
        tr = traces.compose(traces.preemption(5.0, level=1e-3),
                            traces.preemption(5.0, level=1e-3))
        assert tr(5.0) == 1e-6
        assert tr(4.999) == 1.0

    def test_compose_clamps_above_one(self):
        # a misbehaving component (>1) must not push availability past full
        tr = traces.compose(traces.constant(1.8), traces.constant(0.9))
        assert tr(0.0) == 1.0


class TestBoundaries:
    @given(at=times, dur=st.floats(min_value=0.1, max_value=100.0),
           level=levels)
    @settings(max_examples=50, deadline=None)
    def test_preemption_half_open_window(self, at, dur, level):
        restore = at + dur
        tr = traces.preemption(at, restore, level=level)
        assert tr(at) == level          # t == at: already preempted
        assert tr(restore) == 1.0       # t == restore: already back
        assert tr(at + dur / 2) == level
        if at > 0:
            # the float just before `at`: at * (1 - 1e-9) rounds back to
            # `at` when `at` is subnormal
            assert tr(math.nextafter(at, 0.0)) == 1.0

    def test_preemption_without_restore_never_returns(self):
        tr = traces.preemption(3.0, level=0.5)
        assert tr(2.999) == 1.0 and tr(3.0) == 0.5 and tr(1e9) == 0.5

    @given(start=times, dur=st.floats(min_value=0.1, max_value=100.0),
           lo=levels)
    @settings(max_examples=50, deadline=None)
    def test_ramp_endpoints_pinned(self, start, dur, lo):
        tr = traces.ramp(start, dur, lo)
        assert tr(start) == 1.0                       # onset instant: full
        assert math.isclose(tr(start + dur), lo)      # floor exactly at end
        assert math.isclose(tr(start + dur * 10), lo)  # and stays there
        mid = tr(start + dur / 2)
        assert min(1.0, lo) - 1e-12 <= mid <= max(1.0, lo) + 1e-12

    def test_step_interference_half_open(self):
        tr = traces.step_interference(2.0, 4.0, 0.25)
        assert tr(2.0) == 0.25 and tr(4.0) == 1.0 and tr(1.999) == 1.0

    @given(seed=seeds)
    @settings(max_examples=50, deadline=None)
    def test_spike_active_at_its_own_start_instant(self, seed):
        """The off-by-boundary bug this file surfaced: searchsorted with
        side='left' put a spike's start instant BEFORE the spike, so
        trace(start) returned 1.0 instead of the spike level.  The window
        contract is [start, start + spike_len), like every other trace."""
        import numpy as np

        rng = np.random.default_rng(seed)
        n = rng.poisson(2.0 * 300.0 / 100.0)
        starts = np.sort(rng.uniform(0.0, 300.0, size=n))
        tr = traces.random_spikes(seed, horizon=300.0, spike_len=10.0,
                                  level=0.3)
        for s in starts:
            assert tr(float(s)) == 0.3, f"spike at {s} not active at onset"
            assert tr(float(s) + 10.0 - 1e-6) == 0.3
        # and strictly before the first spike: full availability
        if n:
            assert tr(float(starts[0]) - 1e-6) == 1.0
