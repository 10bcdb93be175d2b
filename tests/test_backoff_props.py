"""Backoff-policy properties (hypothesis when available, plus plain
deterministic coverage that always runs): the retry schedule shared by
checkpoint IO, the data prefetcher, and the supervisor must be
monotone-capped, jitter-bounded, attempt-exact, and seed-deterministic —
a wrong schedule either hammers a failing disk or sleeps forever."""
import math

import pytest

from repro.resilience.backoff import BackoffPolicy, TransientError

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                 # not in this container; present in CI
    HAS_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(not HAS_HYPOTHESIS,
                                      reason="hypothesis not installed")


# ---------------------------------------------------------------------------
# deterministic coverage (always runs, container and CI alike)
# ---------------------------------------------------------------------------

def test_raw_delays_monotone_then_capped():
    p = BackoffPolicy(max_attempts=8, base_delay=0.1, multiplier=2.0,
                      max_delay=1.0, jitter=0.0)
    raws = [p.raw_delay(a) for a in range(7)]
    assert raws == sorted(raws)
    assert raws[0] == pytest.approx(0.1)
    assert raws[-1] == 1.0                      # hit the cap
    assert all(r <= 1.0 for r in raws)


def test_delays_are_seed_deterministic():
    p = BackoffPolicy(max_attempts=6, jitter=0.5)
    assert list(p.delays(seed=7)) == list(p.delays(seed=7))
    assert list(p.delays(seed=7)) != list(p.delays(seed=8))


def test_retry_attempt_count_and_success():
    p = BackoffPolicy(max_attempts=4, base_delay=0.01, max_delay=0.01)
    calls = {"n": 0}
    slept = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise TransientError("blip")
        return "ok"

    assert p.retry(flaky, seed=0, sleep=slept.append) == "ok"
    assert calls["n"] == 3 and len(slept) == 2


def test_retry_exhausts_then_raises_last_error():
    p = BackoffPolicy(max_attempts=3, base_delay=0.01, max_delay=0.01)
    calls = {"n": 0}

    def always():
        calls["n"] += 1
        raise TransientError(f"blip {calls['n']}")

    with pytest.raises(TransientError, match="blip 3"):
        p.retry(always, seed=0, sleep=lambda d: None)
    assert calls["n"] == 3                      # exactly max_attempts


def test_retry_nonretryable_propagates_immediately():
    p = BackoffPolicy(max_attempts=5)
    calls = {"n": 0}

    def typo():
        calls["n"] += 1
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        p.retry(typo, retryable=(OSError,), sleep=lambda d: None)
    assert calls["n"] == 1


def test_policy_validation():
    with pytest.raises(ValueError):
        BackoffPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        BackoffPolicy(base_delay=-1.0)
    with pytest.raises(ValueError):
        BackoffPolicy(jitter=1.5)


# ---------------------------------------------------------------------------
# property coverage (CI installs hypothesis; skipped where absent — the
# deterministic tests above still run either way)
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    policies = st.builds(
        BackoffPolicy,
        max_attempts=st.integers(1, 16),
        base_delay=st.floats(1e-3, 1.0),
        multiplier=st.floats(1.0, 4.0),
        max_delay=st.floats(1.0, 60.0),
        jitter=st.floats(0.0, 1.0, exclude_max=True))

    @needs_hypothesis
    @settings(max_examples=100, deadline=None)
    @given(policy=policies)
    def test_prop_raw_delays_monotone_and_capped(policy):
        raws = [policy.raw_delay(a) for a in range(policy.max_attempts)]
        assert all(b >= a for a, b in zip(raws, raws[1:]))
        assert all(0 <= r <= policy.max_delay for r in raws)

    @needs_hypothesis
    @settings(max_examples=100, deadline=None)
    @given(policy=policies, seed=st.integers(0, 2 ** 32 - 1))
    def test_prop_jittered_delays_within_bounds(policy, seed):
        """Every jittered delay stays inside raw*(1 +- jitter) and is
        never negative — the supervisor must not sleep for hours (or
        for -3s)."""
        for attempt, d in enumerate(policy.delays(seed)):
            raw = policy.raw_delay(attempt)
            lo, hi = raw * (1 - policy.jitter), raw * (1 + policy.jitter)
            assert lo - 1e-9 <= d <= hi + 1e-9
            assert d >= 0 and math.isfinite(d)

    @needs_hypothesis
    @settings(max_examples=50, deadline=None)
    @given(policy=policies, seed=st.integers(0, 2 ** 32 - 1))
    def test_prop_delay_stream_seed_deterministic(policy, seed):
        assert list(policy.delays(seed)) == list(policy.delays(seed))
        assert len(list(policy.delays(seed))) == policy.max_attempts - 1

    @needs_hypothesis
    @settings(max_examples=50, deadline=None)
    @given(policy=policies, fail_n=st.integers(0, 20),
           seed=st.integers(0, 2 ** 32 - 1))
    def test_prop_retry_call_counts(policy, fail_n, seed):
        """fn is called min(fail_n+1, max_attempts) times: success stops
        the loop, exhaustion re-raises the final error."""
        calls = {"n": 0}

        def fn():
            calls["n"] += 1
            if calls["n"] <= fail_n:
                raise TransientError("planned")
            return calls["n"]

        if fail_n >= policy.max_attempts:
            with pytest.raises(TransientError):
                policy.retry(fn, seed=seed, sleep=lambda d: None)
            assert calls["n"] == policy.max_attempts
        else:
            assert policy.retry(fn, seed=seed, sleep=lambda d: None) \
                == fail_n + 1
            assert calls["n"] == fail_n + 1
