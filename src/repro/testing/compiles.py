"""Compile counting at JAX's one compilation funnel.

Every real XLA compilation — ``jax.jit`` dispatch, ``lower().compile()``
and the AOT front doors alike — passes through
``jax._src.compiler.backend_compile_and_load``; persistent-cache hits skip
it.  ``count_compiles()`` wraps that function for the duration of a
``with`` block, so it counts true compilation work, not tracing.  This is
the one seam behind the suite's ``compile_counter`` fixture, the
cross-process cache tests and ``benchmarks/serve_bench.py``'s
steady-state compile count.
"""
from __future__ import annotations

import contextlib


class CompileCounter:
    """Modules compiled inside a ``count_compiles()`` block.

    ``names`` holds each compiled module's name in order; ``named(substr)``
    filters by it (e.g. ``'fit_scan_padded'``), which keeps assertions
    robust against incidental helper modules the runtime compiles on the
    side.  ``expect_traces`` pins the *tracing* side via a jitted
    callable's ``_cache_size()``.
    """

    def __init__(self):
        self.names: list[str] = []

    @property
    def compiles(self) -> int:
        return len(self.names)

    def named(self, substr: str) -> int:
        return sum(1 for n in self.names if substr in n)

    @contextlib.contextmanager
    def expect_traces(self, fn, n: int):
        before = fn._cache_size()
        yield
        got = fn._cache_size() - before
        assert got == n, (
            f"expected exactly {n} new trace(s) of "
            f"{getattr(fn, '__name__', fn)}, got {got}"
        )


def _module_name(module) -> str:
    try:
        return str(module.operation.attributes["sym_name"])
    except Exception:
        return str(getattr(module, "name", ""))


@contextlib.contextmanager
def count_compiles():
    """Yield a ``CompileCounter`` that records every compilation made
    until the block exits."""
    from jax._src import compiler

    counter = CompileCounter()
    orig = compiler.backend_compile_and_load

    def spy(backend, module, *args, **kwargs):
        counter.names.append(_module_name(module))
        return orig(backend, module, *args, **kwargs)

    compiler.backend_compile_and_load = spy
    try:
        yield counter
    finally:
        compiler.backend_compile_and_load = orig
