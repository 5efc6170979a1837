"""AOT-compiled, persistently cached envelope traces.

The cold-compile contract (see ``docs/backends.md``):

  * ``backend.fit_padded`` / ``backend.assign_padded`` — the
    envelope-keyed AOT dispatchers over
    ``fused_column.precompile_fit_scan_padded`` /
    ``precompile_assign_padded`` — are bit-identical to calling the
    jitted entry points directly;
  * equal envelopes share ONE compiled executable however the operand
    *values* differ (the cache keys on shapes + statics, never on
    weights/volleys/thresholds), and the shared executable still
    computes per-design results;
  * ``backend.compile_cache(dir)`` makes compilation a cross-process,
    one-time cost: a second process against a populated cache compiles
    ZERO modules and reproduces the first process's results bit for bit
    (sha256 over the raw result bytes — Python ``hash()`` is
    process-randomized and useless here);
  * an unusable cache directory degrades gracefully (RuntimeWarning,
    uncached execution), and a deleted cache dir is recreated on
    re-enable, so a resumed DSE run with a vanished cache keeps going.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend
from repro.core.types import TIME_DTYPE
from repro.kernels import fused_column

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch(seed=0, d=2, p=19, q=4, t_window=21, n=6):
    """A small heterogeneous padded batch with test-unique geometry."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(0, 8, (d, p, q)), jnp.float32)
    xs = jnp.asarray(rng.integers(0, t_window, (n, d, p)), TIME_DTYPE)
    th = jnp.asarray(rng.uniform(3.0, 8.0, (d,)), jnp.float32)
    tm = jnp.asarray(rng.integers(t_window // 2, t_window + 1, (d,)),
                     TIME_DTYPE)
    qa = jnp.asarray(rng.integers(1, q + 1, (d,)), TIME_DTYPE)
    return w, xs, th, tm, qa


def _fit_kw(t_window=21, **over):
    kw = dict(
        t_window=t_window, w_max=7, wta_k=1, mu_capture=1.0,
        mu_backoff=1.0, mu_search=1.0, stabilize=False, response="rnl",
        epochs=2, lowering="reference",
    )
    kw.update(over)
    return kw


@pytest.fixture
def restore_cache_config():
    """Snapshot/restore the global persistent-cache state around tests
    that call ``backend.compile_cache`` for real."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_path = backend._compile_cache_path
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    backend._compile_cache_path = prev_path


# --------------------------------------------- AOT vs jit bit-identity
def test_fit_and_assign_dispatchers_bit_identical_to_jit_path():
    w, xs, th, tm, qa = _batch(seed=1)
    kw = _fit_kw()
    # fresh weight buffers: the fit scan donates its first argument
    w_jit = fused_column.fit_scan_padded(jnp.array(w), xs, th, tm, qa, **kw)
    w_aot = backend.fit_padded(jnp.array(w), xs, th, tm, qa, **kw)
    np.testing.assert_array_equal(np.asarray(w_jit), np.asarray(w_aot))
    akw = dict(t_window=21, wta_k=1, response="rnl", lowering="reference")
    ids_jit = fused_column.assign_padded(w_jit, xs, th, tm, qa, **akw)
    ids_aot = backend.assign_padded(w_aot, xs, th, tm, qa, **akw)
    np.testing.assert_array_equal(np.asarray(ids_jit), np.asarray(ids_aot))


def test_precompile_needs_no_operands_and_matches_warm_call():
    """The ISSUE's precompile contract: an executable built from shapes
    alone (``jit(...).lower().compile()``) is the very program the jit
    path runs — a service can compile its envelope set before any data
    exists."""
    w, xs, th, tm, qa = _batch(seed=2, d=3, p=17, q=3, t_window=19, n=5)
    kw = _fit_kw(t_window=19)
    exe = fused_column.precompile_fit_scan_padded(
        3, 17, 3, 5, t_window=19, w_max=7, wta_k=1, stabilize=False,
        response="rnl", epochs=2, lowering="reference",
    )
    got = exe(
        jnp.array(w), xs, th, tm, qa,
        mu_capture=jnp.float32(1.0), mu_backoff=jnp.float32(1.0),
        mu_search=jnp.float32(1.0),
    )
    want = fused_column.fit_scan_padded(jnp.array(w), xs, th, tm, qa, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    aexe = fused_column.precompile_assign_padded(
        3, 17, 3, 5, t_window=19, wta_k=1, response="rnl",
        lowering="reference",
    )
    np.testing.assert_array_equal(
        np.asarray(aexe(want, xs, th, tm, qa)),
        np.asarray(fused_column.assign_padded(
            want, xs, th, tm, qa, t_window=19, wta_k=1, response="rnl",
            lowering="reference",
        )),
    )


# ----------------------------------------------- envelope cache keying
def test_equal_envelopes_share_one_executable_but_not_results(
    compile_counter,
):
    """Cache-key collision test: two batches with equal envelopes but
    different runtime operands hit ONE executable (a single backend
    compile, a single AOT cache entry) and still diverge numerically —
    the cache keys programs, never values."""
    kw = _fit_kw(t_window=23)
    w1, xs1, th1, tm1, qa1 = _batch(seed=3, d=2, p=23, q=3, t_window=23)
    w2, xs2, th2, tm2, qa2 = _batch(seed=4, d=2, p=23, q=3, t_window=23)
    backend.aot_cache_clear()
    r1 = backend.fit_padded(w1, xs1, th1, tm1, qa1, **kw)
    grown = backend.aot_cache_size()
    r2 = backend.fit_padded(w2, xs2, th2, tm2, qa2, **kw)
    assert backend.aot_cache_size() == grown == 1
    assert compile_counter.named("fit_scan_padded") == 1, (
        "the second equal-envelope batch must reuse the first executable"
    )
    assert not np.array_equal(np.asarray(r1), np.asarray(r2)), (
        "shared executable, divergent operands -> divergent results"
    )
    # a different envelope (v_blk via a different N) is a new executable
    w3, xs3, th3, tm3, qa3 = _batch(seed=3, d=2, p=23, q=3, t_window=23,
                                    n=9)
    backend.fit_padded(w3, xs3, th3, tm3, qa3, **kw)
    assert backend.aot_cache_size() == 2


# ------------------------------------------- persistent cache round-trip
_CHILD = textwrap.dedent("""
    import json, hashlib, sys
    import numpy as np
    import jax.numpy as jnp
    from repro.core import backend
    from repro.core.types import TIME_DTYPE
    from repro.testing import count_compiles

    with count_compiles() as counts:
        assert backend.compile_cache(sys.argv[1]) is not None
        rng = np.random.default_rng(7)
        w = jnp.asarray(rng.integers(0, 8, (2, 27, 3)), jnp.float32)
        xs = jnp.asarray(rng.integers(0, 18, (5, 2, 27)), TIME_DTYPE)
        th = jnp.asarray([6.0, 4.0], jnp.float32)
        tm = jnp.asarray([18, 14], TIME_DTYPE)
        qa = jnp.asarray([3, 2], TIME_DTYPE)
        w2 = backend.fit_padded(
            w, xs, th, tm, qa, t_window=18, w_max=7, wta_k=1,
            mu_capture=1.0, mu_backoff=1.0, mu_search=1.0,
            stabilize=False, response="rnl", epochs=2, lowering="reference",
        )
        ids = backend.assign_padded(
            w2, xs, th, tm, qa, t_window=18, wta_k=1, response="rnl",
            lowering="reference",
        )
        digest = hashlib.sha256(
            np.asarray(w2).tobytes() + np.asarray(ids).tobytes()
        ).hexdigest()
    print(json.dumps({
        "compiles": counts.compiles,
        "fit_compiles": counts.named("fit_scan_padded"),
        "digest": digest,
    }))
""")


def _run_child(cache_dir: str) -> dict:
    env = dict(os.environ, PYTHONPATH="src")
    # the child owns its cache dir; an outer cache must not leak in
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, cache_dir],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=600,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_second_process_compiles_zero_envelope_traces(tmp_path):
    """The tentpole acceptance: with a populated persistent cache, a
    fresh process compiles NOTHING — not the envelope traces, not the
    helper modules — and its results are bit-identical to the process
    that paid the compile."""
    cache = str(tmp_path / "compile_cache")
    first = _run_child(cache)
    assert first["fit_compiles"] == 1, first
    assert first["compiles"] >= 1
    second = _run_child(cache)
    assert second["compiles"] == 0, (
        f"second process recompiled {second['compiles']} modules with a "
        "populated persistent cache"
    )
    assert second["digest"] == first["digest"], (
        "cached executables must reproduce the original results bit for "
        "bit"
    )


# ------------------------------------------------------ graceful fallback
def test_unusable_cache_dir_warns_and_runs_uncached(restore_cache_config):
    """A cache path that cannot be a directory (here: nested under a
    regular file) must degrade to uncached execution, not break the run.
    (A chmod-based read-only probe is useless in rootful CI containers —
    root writes anywhere — so the unusable path IS the fallback case.)"""
    probe_file = os.path.join(REPO, "README.md")
    with pytest.warns(RuntimeWarning, match="compilation cache disabled"):
        assert backend.compile_cache(
            os.path.join(probe_file, "sub")
        ) is None
    # compilation still works, just in-process
    w, xs, th, tm, qa = _batch(seed=5, d=2, p=13, q=3, t_window=15, n=4)
    out = backend.fit_padded(w, xs, th, tm, qa, **_fit_kw(t_window=15))
    assert np.isfinite(np.asarray(out)).all()


def test_deleted_cache_dir_is_recreated(tmp_path, restore_cache_config):
    """Re-enabling after the directory vanished (the resumed-DSE case)
    repairs it instead of failing."""
    d = str(tmp_path / "cache")
    assert backend.compile_cache(d) == d
    assert backend.compile_cache_dir() == d
    shutil.rmtree(d)
    assert backend.compile_cache(d) == d
    assert os.path.isdir(d)
