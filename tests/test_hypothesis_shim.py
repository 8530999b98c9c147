"""Shim-contract tests: the deterministic hypothesis stand-in vs the real
library.

``tests/conftest.py`` installs ``_hypothesis_shim`` as
``sys.modules["hypothesis"]`` only when the genuine package is absent, so
on a box with hypothesis installed the shim would otherwise go untested —
and vice versa.  This file closes the gap: one tiny property (the exact
strategy slice ``test_sparse.py`` leans on — ``integers``, ``sampled_from``,
``booleans``, ``lists(unique=...)``, ``composite``) runs under the shim
*loaded explicitly from its file*, and the same property runs again under
whatever ``import hypothesis`` resolves to.  When that resolves to the shim
(real library missing), the second run is skipped rather than duplicated.
"""

import importlib.util
import os

import pytest


def _load_shim():
    spec = importlib.util.spec_from_file_location(
        "_shim_under_test",
        os.path.join(os.path.dirname(__file__), "_hypothesis_shim.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _real_hypothesis():
    """The installed hypothesis, or None when conftest swapped in the shim."""
    import hypothesis

    path = getattr(hypothesis, "__file__", "") or ""
    if path.endswith("_hypothesis_shim.py"):
        return None
    return hypothesis


def _run_contract_property(hyp, st):
    """The shared property: draws must respect bounds and uniqueness.

    Returns the number of executed examples so callers can assert the
    engine actually swept cases instead of passing vacuously.
    """
    executed = []

    @hyp.given(
        n=st.integers(min_value=1, max_value=8),
        dens=st.sampled_from([0.01, 0.1, 0.5]),
        flag=st.booleans(),
        cols=st.lists(st.integers(min_value=0, max_value=15),
                      min_size=0, max_size=10, unique=True),
    )
    def prop(n, dens, flag, cols):
        assert 1 <= n <= 8
        assert dens in (0.01, 0.1, 0.5)
        assert isinstance(flag, bool)
        assert all(0 <= c <= 15 for c in cols)
        assert len(set(cols)) == len(cols)
        executed.append(1)

    prop()
    return len(executed)


class TestShimContract:
    def test_property_under_shim(self):
        shim = _load_shim()
        assert _run_contract_property(shim, shim.strategies) >= 10

    def test_property_under_real_hypothesis(self):
        hyp = _real_hypothesis()
        if hyp is None:
            pytest.skip("real hypothesis not installed (shim active)")
        import hypothesis.strategies as st

        assert _run_contract_property(hyp, st) >= 10

    def test_unique_by_under_shim(self):
        shim = _load_shim()
        st = shim.strategies
        pairs = st.lists(
            st.tuples(st.integers(0, 3), st.integers(0, 100)),
            min_size=2, max_size=4, unique_by=lambda p: p[0])
        import random

        rng = random.Random(7)
        for _ in range(50):
            try:
                drawn = pairs.sample(rng)
            except shim._Assumption:
                continue  # bounded redraw exhausted: rejected, not hung
            keys = [p[0] for p in drawn]
            assert len(set(keys)) == len(keys)

    def test_shim_unsatisfiable_is_loud(self):
        shim = _load_shim()

        @shim.given(x=shim.strategies.integers(0, 10))
        def prop(x):
            shim.assume(False)

        with pytest.raises(AssertionError, match="rejected all"):
            prop()

    def test_shim_unique_exhaustion_rejects_sample(self):
        # 5 unique draws demanded from a 3-value space: every sample must
        # exhaust the redraw budget and reject — given() then raises its
        # Unsatisfiable mirror instead of looping forever or passing.
        shim = _load_shim()
        st = shim.strategies

        @shim.given(v=st.lists(st.integers(0, 2), min_size=5, max_size=5,
                               unique=True))
        def prop(v):
            raise AssertionError("unreachable: sample cannot be satisfied")

        with pytest.raises(AssertionError, match="rejected all"):
            prop()

    def test_shim_example_runs_first(self):
        # @example cases run before any draw, with the drawn argument's name
        shim = _load_shim()
        seen = []

        @shim.given(x=shim.strategies.integers(5, 9))
        @shim.example(x=1)
        @shim.settings(max_examples=3)
        def prop(x):
            seen.append(x)

        prop()
        assert seen[0] == 1 and len(seen) == 4
        assert all(5 <= x <= 9 for x in seen[1:])
