"""Property tests for the reliability layer (repro.reliability).

Three contracts under arbitrary inputs:

1. **Snapshot round-trip** — any dict of arrays (arbitrary dtypes/shapes,
   including empty arrays) plus any JSON-able metadata survives
   ``save_snapshot → load_snapshot`` value- and dtype-identically.
2. **Keep-N** — after any sequence of snapshot saves, exactly the newest
   ``keep`` sweeps remain on disk and ``latest_snapshot`` names the
   newest; no tmp debris survives a save.
3. **Injector determinism** — a ``FaultPlan`` is a pure function of
   ``(specs, seed)``: two injectors built from equal plans make identical
   fire/pass decisions for any identity stream, and the per-spec
   ``times`` budget is never exceeded.
"""
import os
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.reliability import (
    FaultPlan,
    FaultSpec,
    TransientFault,
    latest_snapshot,
    list_snapshots,
    load_snapshot,
    save_snapshot,
)

_dtypes = st.sampled_from(
    [np.float32, np.float64, np.int32, np.int64, np.uint8, np.bool_]
)


@st.composite
def _array(draw):
    dtype = draw(_dtypes)
    shape = draw(
        st.lists(st.integers(min_value=0, max_value=5), min_size=0, max_size=3)
    )
    rng = np.random.default_rng(draw(st.integers(0, 2**32 - 1)))
    if dtype == np.bool_:
        return rng.random(shape) < 0.5
    if np.issubdtype(dtype, np.floating):
        return rng.standard_normal(shape).astype(dtype)
    return rng.integers(0, 100, size=shape).astype(dtype)


_arrays = st.dictionaries(
    st.text(
        alphabet=st.characters(min_codepoint=97, max_codepoint=122),
        min_size=1,
        max_size=8,
    ),
    _array(),
    min_size=0,
    max_size=4,
)

_meta = st.dictionaries(
    st.text(
        alphabet=st.characters(min_codepoint=97, max_codepoint=122),
        min_size=1,
        max_size=8,
    ),
    st.one_of(
        st.integers(min_value=-(2**31), max_value=2**31),
        st.floats(allow_nan=False, allow_infinity=False, width=32),
        st.text(max_size=16),
        st.booleans(),
    ),
    max_size=4,
)


@settings(max_examples=40, deadline=None)
@given(arrays=_arrays, meta=_meta, sweep=st.integers(0, 10**6))
def test_snapshot_round_trip(arrays, meta, sweep):
    with tempfile.TemporaryDirectory() as d:
        path = save_snapshot(d, sweep, arrays, meta, keep=1)
        got_arrays, got_meta = load_snapshot(path)
        assert got_meta == meta
        assert set(got_arrays) == set(arrays)
        for k, a in arrays.items():
            assert got_arrays[k].dtype == a.dtype
            assert got_arrays[k].shape == a.shape
            assert np.array_equal(got_arrays[k], a)


@settings(max_examples=25, deadline=None)
@given(
    sweeps=st.lists(
        st.integers(1, 50), min_size=1, max_size=8, unique=True
    ),
    keep=st.integers(1, 4),
)
def test_keep_n_retention(sweeps, keep):
    with tempfile.TemporaryDirectory() as d:
        for s in sorted(sweeps):
            save_snapshot(d, s, {"x": np.arange(3)}, {"sweep": s}, keep=keep)
        kept = list_snapshots(d)
        expected = sorted(sweeps)[-keep:]
        assert [os.path.basename(p) for p in kept] == [
            f"sweep_{s:08d}.npz" for s in expected
        ]
        assert latest_snapshot(d) == kept[-1]
        assert not [n for n in os.listdir(d) if n.endswith(".tmp")]


@settings(max_examples=40, deadline=None)
@given(
    seed=st.integers(0, 2**31),
    rate=st.floats(min_value=0.0, max_value=1.0),
    times=st.one_of(st.none(), st.integers(1, 8)),
    n=st.integers(1, 64),
)
def test_injector_is_deterministic_and_budgeted(seed, rate, times, n):
    plan = FaultPlan(
        specs=(FaultSpec(site="h2d", kind="transient", rate=rate, times=times),),
        seed=seed,
    )

    def run():
        inj = plan.injector()
        out = []
        for i in range(n):
            try:
                inj.check("h2d", f"xfer:{i}")
                out.append(0)
            except TransientFault:
                out.append(1)
        return out, inj.fired()

    (a, fired_a), (b, fired_b) = run(), run()
    assert a == b and fired_a == fired_b
    if times is not None:
        assert fired_a <= times


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31), n=st.integers(1, 32))
def test_storage_decisions_deterministic(seed, n):
    plan = FaultPlan.storage_corrupt("seg", times=3, seed=seed)

    def run():
        inj = plan.injector()
        return [inj.storage_read("seg_a", attempt) for attempt in range(n)]

    assert run() == run()
    # attempt-indexed: corrupt exactly while attempt < times
    decisions = run()
    for attempt, d in enumerate(decisions):
        assert d == ("corrupt" if attempt < 3 else None)


def test_zero_times_budget_rejected():
    """``times`` counts faults to fire: 0 would be a spec that never
    fires, so it is refused (``None`` means unlimited)."""
    with pytest.raises(ValueError, match="times"):
        FaultSpec(site="h2d", kind="transient", rate=1.0, times=0)
