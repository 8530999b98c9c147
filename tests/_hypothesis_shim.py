"""Deterministic stand-in for ``hypothesis`` when it is not installed.

The property tests in this suite use a small slice of the hypothesis API:
``given``/``settings`` plus the ``integers``/``floats``/``lists``/``tuples``/
``sampled_from``/``booleans``/``composite``/``data`` strategies, including
``lists(..., unique=True)``/``unique_by`` (uniqueness via bounded redraw —
the sparse CSR strategies draw unique sorted column indices per row).  This
shim implements exactly that slice
with a seeded PRNG so the tests still sweep many pseudo-random cases — just
without shrinking, replay databases, or health checks.  ``tests/conftest.py``
installs it as ``sys.modules["hypothesis"]`` only when the real package is
missing; with hypothesis installed (see requirements-dev.txt) the genuine
library is used unchanged.
"""

from __future__ import annotations

import functools
import inspect
import random
import types
import zlib

DEFAULT_MAX_EXAMPLES = 25


class _Strategy:
    """A sampler: ``sample(rng)`` draws one value."""

    def __init__(self, sample):
        self._sample = sample

    def sample(self, rng):
        return self._sample(rng)


def _integers(min_value, max_value):
    return _Strategy(lambda rng: rng.randint(min_value, max_value))


def _floats(min_value, max_value):
    return _Strategy(lambda rng: rng.uniform(min_value, max_value))


def _lists(elements, min_size=0, max_size=10, unique=False, unique_by=None):
    key = unique_by if unique_by is not None else (
        (lambda v: v) if unique else None)

    def sample(rng):
        k = rng.randint(min_size, max_size)
        if key is None:
            return [elements.sample(rng) for _ in range(k)]
        # uniqueness via bounded redraw — mirrors hypothesis semantics for
        # the small discrete element spaces this suite draws (e.g. CSR
        # column indices); an exhausted budget rejects the sample like a
        # failed assume() rather than looping forever
        out, seen = [], set()
        budget = 200 * max(1, k)
        while len(out) < k and budget:
            budget -= 1
            v = elements.sample(rng)
            kv = key(v)
            if kv in seen:
                continue
            seen.add(kv)
            out.append(v)
        if len(out) < min_size:
            raise _Assumption()
        return out

    return _Strategy(sample)


def _tuples(*elems):
    return _Strategy(lambda rng: tuple(e.sample(rng) for e in elems))


def _sampled_from(seq):
    seq = list(seq)
    return _Strategy(lambda rng: seq[rng.randrange(len(seq))])


def _booleans():
    return _Strategy(lambda rng: bool(rng.getrandbits(1)))


class _Data:
    """Interactive draw object handed out by the ``data()`` strategy."""

    def __init__(self, rng):
        self._rng = rng

    def draw(self, strategy, label=None):
        return strategy.sample(self._rng)


def _data():
    return _Strategy(_Data)


def _composite(fn):
    """``@st.composite`` — ``fn(draw, *args)`` becomes a strategy factory."""

    @functools.wraps(fn)
    def build(*args, **kwargs):
        def sample(rng):
            return fn(lambda strat: strat.sample(rng), *args, **kwargs)

        return _Strategy(sample)

    return build


class _Assumption(Exception):
    pass


def assume(condition):
    if not condition:
        raise _Assumption()
    return True


def settings(*args, **kwargs):
    """Records ``max_examples`` for ``given``; every other knob is a no-op."""

    def deco(fn):
        fn._shim_settings = dict(kwargs)
        return fn

    return deco


class HealthCheck:  # attribute access only (settings(suppress_health_check=…))
    all = ()
    too_slow = None
    data_too_large = None
    filter_too_much = None


def example(*args, **kwargs):
    """``@example`` — an explicit case ``given`` runs before any draw."""

    def deco(fn):
        fn._shim_examples = [(args, kwargs)] + list(
            getattr(fn, "_shim_examples", []))
        return fn

    return deco


def given(*arg_strategies, **kw_strategies):
    if arg_strategies and kw_strategies:
        raise TypeError("shim: mixing positional and keyword strategies")

    def deco(fn):
        max_examples = getattr(fn, "_shim_settings", {}).get(
            "max_examples", DEFAULT_MAX_EXAMPLES)

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            # Seed from the test's qualified name: deterministic across runs,
            # distinct across tests.
            rng = random.Random(zlib.crc32(fn.__qualname__.encode()))
            for ex_args, ex_kwargs in getattr(fn, "_shim_examples", []):
                fn(*args, *ex_args, **kwargs, **ex_kwargs)
            executed = 0
            for _ in range(max_examples):
                try:
                    if arg_strategies:
                        drawn = [s.sample(rng) for s in arg_strategies]
                        fn(*args, *drawn, **kwargs)
                    else:
                        drawn = {k: s.sample(rng)
                                 for k, s in kw_strategies.items()}
                        fn(*args, **kwargs, **drawn)
                    executed += 1
                except _Assumption:
                    continue
            if executed == 0:
                # Mirror hypothesis's Unsatisfiable: a test whose assume()
                # rejected every sample must not pass vacuously.
                raise AssertionError(
                    f"shim: assume() rejected all {max_examples} examples "
                    f"for {fn.__qualname__}")

        # Hide the drawn parameters from pytest's fixture resolution (the
        # real hypothesis does the same): expose only the untouched ones.
        sig = inspect.signature(fn)
        params = list(sig.parameters.values())
        if arg_strategies:
            params = params[: len(params) - len(arg_strategies)]
        else:
            params = [p for p in params if p.name not in kw_strategies]
        wrapper.__signature__ = sig.replace(parameters=params)
        return wrapper

    return deco


strategies = types.ModuleType("hypothesis.strategies")
strategies.integers = _integers
strategies.floats = _floats
strategies.lists = _lists
strategies.tuples = _tuples
strategies.sampled_from = _sampled_from
strategies.booleans = _booleans
strategies.data = _data
strategies.composite = _composite
