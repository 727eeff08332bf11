"""Graceful degradation when `hypothesis` is absent.

`hypothesis` is a declared dev extra (``pip install -e '.[dev]'``), but the
suite must still collect and run its non-property tests without it. Property
tests import through this shim:

    from _hypothesis_compat import (HAVE_HYPOTHESIS, assume, example, given,
                                    settings, st)

With hypothesis installed this re-exports the real API unchanged. Without
it, ``@given(...)`` turns each property test into an individually-skipped
test (reason: "hypothesis not installed") instead of breaking collection of
its whole module.
"""
import pytest

try:
    from hypothesis import assume, example, given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    def given(*_args, **_kwargs):
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    def example(*_args, **_kwargs):
        return lambda fn: fn

    def assume(_condition):
        return True

    class _AnyStrategy:
        """Stand-in for hypothesis.strategies: every strategy constructor
        returns an inert placeholder (the tests are skipped anyway)."""

        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _AnyStrategy()

__all__ = ["HAVE_HYPOTHESIS", "assume", "example", "given", "settings",
           "st"]
