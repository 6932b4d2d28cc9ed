"""Shared fixtures. NOTE: tests run on the single host CPU device —
XLA_FLAGS device-count forcing is reserved for launch/dryrun_lda.py and the
subprocess-based distribution tests."""
import functools
import inspect
import sys
import types
import zlib

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


def _install_hypothesis_fallback():
    """Deterministic stand-in for the hypothesis surface this suite uses.

    Without the real package, ``from hypothesis import given, ...`` used to
    *error five test modules out of collection*. This fallback runs each
    property test on a fixed number of seeded random examples instead —
    collection always succeeds, and installing the real dependency
    (``pip install -e .[test]``) transparently restores full
    shrinking/replay behaviour. Only the strategies the suite draws from
    are provided: integers / booleans / sampled_from.
    """
    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    class _UnsatisfiedAssumption(Exception):
        """Raised by assume(False): discard the example, like hypothesis."""

    def assume(condition):
        if not condition:
            raise _UnsatisfiedAssumption
        return True

    st = types.ModuleType("hypothesis.strategies")
    st.integers = lambda min_value=0, max_value=2**31 - 1: _Strategy(
        lambda r: int(r.integers(min_value, max_value + 1)))
    st.booleans = lambda: _Strategy(lambda r: bool(r.integers(0, 2)))
    st.sampled_from = lambda seq: _Strategy(
        lambda r, _s=tuple(seq): _s[int(r.integers(0, len(_s)))])

    def given(**strategies):
        def deco(fn):
            @functools.wraps(fn)
            def run(*args, **kwargs):
                n = getattr(run, "_max_examples", 10)
                rng = np.random.default_rng(zlib.crc32(fn.__name__.encode()))
                runs = 0
                for _ in range(n * 20):       # bounded redraws for assume()
                    if runs == n:
                        break
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    try:
                        fn(*args, **kwargs, **drawn)
                        runs += 1
                    except _UnsatisfiedAssumption:
                        continue
                if runs == 0:
                    pytest.skip("hypothesis fallback: no example satisfied "
                                "assume()")
            # drawn params are not fixtures: hide them from pytest
            run.__signature__ = inspect.Signature(parameters=[])
            return run
        return deco

    def settings(max_examples=10, **_ignored):
        def deco(fn):
            fn._max_examples = max_examples
            return fn
        return deco

    mod = types.ModuleType("hypothesis")
    mod.given, mod.settings, mod.strategies = given, settings, st
    mod.assume = assume
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st


try:
    import hypothesis  # noqa: F401  (the real one, if installed)
except ImportError:
    _install_hypothesis_fallback()


@pytest.fixture(scope="session")
def tiny_corpus():
    from repro.data import PAPER_CORPORA, make_corpus
    spec = PAPER_CORPORA["tiny"]
    return (make_corpus(spec, split="train", seed=0),
            make_corpus(spec, split="test", seed=0), spec)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
