"""Property tests (hypothesis) for traces, data engine, admission math."""

import math

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need the optional hypothesis dependency")
from hypothesis import given, settings, strategies as st

from repro.core import GPU_H800, ProfileStore
from repro.core.datastore import DataEngine
from repro.sim import gamma_interarrivals, generate_trace
from repro.sim.trace import skewed_popularity


@given(rate=st.floats(0.1, 20), cv=st.floats(0.25, 8))
@settings(max_examples=25, deadline=None)
def test_gamma_interarrival_moments(rate, cv):
    rng = np.random.default_rng(0)
    x = gamma_interarrivals(rate, 20000, cv, rng)
    assert x.mean() == pytest.approx(1 / rate, rel=0.1)
    assert x.std() / x.mean() == pytest.approx(cv, rel=0.15)


@given(n=st.integers(2, 12), alpha=st.floats(0.5, 2.5))
@settings(max_examples=25, deadline=None)
def test_popularity_is_distribution(n, alpha):
    p = skewed_popularity([f"w{i}" for i in range(n)], alpha)
    assert p.sum() == pytest.approx(1.0)
    assert all(p[i] >= p[i + 1] for i in range(n - 1))


@given(st.data())
@settings(max_examples=40, deadline=None)
def test_data_engine_refcount_invariant(data):
    """Values vanish exactly when their last consumer releases them."""
    engine = DataEngine(ProfileStore(GPU_H800))
    n = data.draw(st.integers(1, 10))
    keys = []
    for i in range(n):
        rc = data.draw(st.integers(1, 4))
        engine.put(f"k{i}", executor_id=0, nbytes=100, refcount=rc)
        keys.append((f"k{i}", rc))
    for key, rc in keys:
        for j in range(rc):
            assert engine.exists(key)
            engine.release(key)
        assert not engine.exists(key)
    assert len(engine) == 0


@given(st.lists(st.integers(0, 3), min_size=1, max_size=8))
@settings(max_examples=25, deadline=None)
def test_fetch_is_idempotent_per_executor(placements):
    engine = DataEngine(ProfileStore(GPU_H800))
    engine.put("k", executor_id=0, nbytes=10**6, refcount=100)
    total_before = engine.bytes_transferred
    for e in placements:
        engine.fetch("k", e)
    # second pass must be all local hits
    transfers_after_first = engine.num_transfers
    for e in placements:
        engine.fetch("k", e)
    assert engine.num_transfers == transfers_after_first


def test_trace_sorted_and_in_window():
    tr = generate_trace(["a", "b"], rate=3.0, duration=50, cv=2.0, seed=9)
    arr = [t.arrival for t in tr]
    assert arr == sorted(arr)
    assert all(0 <= a < 50 for a in arr)
