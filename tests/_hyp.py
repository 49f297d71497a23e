"""Import hypothesis, or stub it so only @given tests skip.

A module-level ``pytest.importorskip("hypothesis")`` would hide every
test in the file when hypothesis is absent — including plain tests that
never touch it. Importing ``given``/``settings``/``st`` from here
instead keeps those running: without hypothesis, ``@given`` becomes a
skip marker, ``@example`` a no-op and ``st`` a chainable dummy whose
strategies are never executed.
"""
import pytest

try:
    from hypothesis import (  # noqa: F401
        example, given, settings, strategies as st)

    HAS_HYPOTHESIS = True
except ModuleNotFoundError:  # pragma: no cover - depends on environment
    HAS_HYPOTHESIS = False

    class _DummyStrategy:
        """Absorbs any strategy construction (st.integers(...).filter(...))."""

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

    st = _DummyStrategy()

    def given(*args, **kwargs):
        return pytest.mark.skip(reason="property test requires hypothesis")

    def settings(*args, **kwargs):
        def deco(fn):
            return fn

        return deco

    example = settings
