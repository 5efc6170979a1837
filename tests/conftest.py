"""Test-suite bootstrap: the ``compile_counter`` fixture and a tiny
vendored ``hypothesis`` shim.

Several test modules hard-import ``hypothesis``; the container does not ship
it and nothing may be pip-installed.  Instead of skipping those modules (and
losing their coverage), we register a minimal drop-in shim into
``sys.modules`` *before collection* that supports exactly the API surface the
suite uses:

  @settings(max_examples=N, deadline=None)
  @given(x=st.integers(a, b), y=st.floats(a, b), z=st.sampled_from(seq))
  def test_...(x, y, z): ...

The shim draws ``max_examples`` pseudo-random examples per test from a
deterministic per-test seed (derived from the test name), so runs are
reproducible.  There is no shrinking and no example database — it is a test
*runner*, not a property-based testing engine.  If the real ``hypothesis``
is installed it is used untouched.
"""
from __future__ import annotations

import functools
import random
import sys
import types
import zlib

import pytest


@pytest.fixture
def compile_counter(monkeypatch, tmp_path):
    """Count compilations at JAX's compile funnel
    (``repro.testing.count_compiles``).

    Every trace-count / compile-count assertion in the suite goes through
    this fixture — one seam, one contract.  Both persistence layers — the
    JAX compilation cache AND the serialized-AOT-executable store keyed
    off ``backend.compile_cache_dir()`` — are pointed at a throwaway
    per-test directory for the fixture's lifetime, so counts are
    deterministic regardless of whether the environment runs the suite
    with a warm ``JAX_COMPILATION_CACHE_DIR``.
    """
    import jax
    from repro.core import backend as _backend
    from repro.testing import count_compiles

    monkeypatch.setattr(
        _backend, "_compile_cache_path", str(tmp_path / "jaxcache")
    )
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jaxcache"))
    try:
        with count_compiles() as counter:
            yield counter
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


try:  # real hypothesis wins if present
    import hypothesis  # noqa: F401
except ImportError:
    _MODULE = "hypothesis"

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example_from(self, rng: random.Random):
            return self._draw(rng)

    def integers(min_value, max_value):
        return _Strategy(lambda rng: rng.randint(min_value, max_value))

    def floats(min_value, max_value):
        return _Strategy(lambda rng: rng.uniform(min_value, max_value))

    def sampled_from(elements):
        seq = list(elements)
        return _Strategy(lambda rng: seq[rng.randrange(len(seq))])

    def given(**strategies):
        def decorate(fn):
            @functools.wraps(fn)
            def runner():  # zero-arg: examples are drawn, not fixtures
                cfg = getattr(runner, "_shim_settings", {})
                n = cfg.get("max_examples", 10)
                seed = zlib.adler32(fn.__qualname__.encode())
                rng = random.Random(seed)
                for _ in range(n):
                    kwargs = {
                        name: s.example_from(rng)
                        for name, s in strategies.items()
                    }
                    try:
                        fn(**kwargs)
                    except Exception as e:  # surface the failing example
                        raise AssertionError(
                            f"falsifying example ({fn.__name__}): {kwargs}"
                        ) from e

            # pytest resolves fixtures from inspect.signature, which follows
            # __wrapped__ back to fn's (example-)parameters — drop it.
            del runner.__wrapped__
            return runner

        return decorate

    def settings(**kwargs):
        def decorate(fn):
            fn._shim_settings = kwargs
            return fn

        return decorate

    shim = types.ModuleType(_MODULE)
    shim.given = given
    shim.settings = settings
    shim.__version__ = "0.0-shim"
    strategies_mod = types.ModuleType(f"{_MODULE}.strategies")
    strategies_mod.integers = integers
    strategies_mod.floats = floats
    strategies_mod.sampled_from = sampled_from
    shim.strategies = strategies_mod
    sys.modules[_MODULE] = shim
    sys.modules[f"{_MODULE}.strategies"] = strategies_mod
