"""Shared test/benchmark instrumentation for the repro package.

``repro.testing.faults`` is the deterministic fault-injection harness at
the ``fused_column`` seam — the single library behind the fault tests,
the serving chaos tests and ``benchmarks/serve_bench.py``'s chaos case.
``repro.testing.compiles`` counts XLA compilations at JAX's compile
funnel (``count_compiles``) for the same three consumers.
"""
from repro.testing import faults
from repro.testing.compiles import CompileCounter, count_compiles

__all__ = ["faults", "CompileCounter", "count_compiles"]
