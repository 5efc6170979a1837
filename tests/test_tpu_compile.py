"""The Mosaic kernels of the main path compile for a TPU v5e.

Interpret mode runs the kernel bodies but never asks the chip's compiler
about tiling, block shapes or iota dtypes, so a kernel can pass every
interpret-mode test and still be refused on the chip.  These tests
AOT-compile each kernel entry point at the paper's Table II envelope for
a *described* ``v5e:2x2`` topology — the TPU compiler is installed even
where no chip is attached — and check that the Mosaic kernel is in the
compiled program.  Nothing runs; results are covered by the
interpret-mode tests.

The topology is described inside a module-scoped fixture (never at
import: one process at a time may load the TPU library), and every test
of this kind lives in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import TIME_DTYPE
from repro.kernels import fused_column, rnl_response

# Table II envelope: 7 designs, p up to 637 (-> 640 lanes), q up to 25
# (-> 32), the simulator's 64-cycle gamma window.
D, P_PAD, Q_PAD, T_WINDOW, N_VOLLEYS = 7, 640, 32, 64, 64
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _padded_operands(spec, d=D):
    return (
        spec((d, P_PAD, Q_PAD), F32),                 # w
        spec((N_VOLLEYS, d, P_PAD), TIME_DTYPE),      # xs
        spec((d,), F32),                              # thresholds
        spec((d,), TIME_DTYPE),                       # t_maxes
        spec((d,), TIME_DTYPE),                       # q_actives
    )


def _assert_mosaic(compiled):
    assert "tpu_custom_call" in compiled.as_text(), (
        "the compiled program holds no Mosaic kernel"
    )


def test_fit_scan_padded_compiles_for_v5e(spec):
    mu = spec((), F32)
    compiled = fused_column.fit_scan_padded.lower(
        *_padded_operands(spec), t_window=T_WINDOW, w_max=7, wta_k=1,
        mu_capture=mu, mu_backoff=mu, mu_search=mu, stabilize=True,
        response="rnl", epochs=1, lowering="mosaic", t_blk=128, v_blk=32,
    ).compile()
    _assert_mosaic(compiled)


def test_stochastic_fit_scan_padded_compiles_for_v5e(spec):
    """The stochastic envelope: the stream's Threefry rounds in int32 VPU
    ops inside the fit kernel, with the keys and volley base in SMEM."""
    mu = spec((), F32)
    compiled = fused_column.fit_scan_padded.lower(
        *_padded_operands(spec), t_window=T_WINDOW, w_max=7, wta_k=1,
        mu_capture=mu, mu_backoff=mu, mu_search=mu, stabilize=True,
        response="rnl", epochs=1, lowering="mosaic", t_blk=128, v_blk=32,
        stochastic=True, keys=spec((D, 2), jnp.int32),
    ).compile()
    _assert_mosaic(compiled)


def test_resumed_stochastic_fit_compiles_for_v5e(spec):
    """The streaming service's re-fit: the stochastic envelope resumed at
    a runtime stream index ``v0`` over a 32-volley window."""
    mu = spec((), F32)
    d, n = 3, 32
    compiled = fused_column.fit_scan_padded.lower(
        spec((d, P_PAD, Q_PAD), F32), spec((n, d, P_PAD), TIME_DTYPE),
        spec((d,), F32), spec((d,), TIME_DTYPE), spec((d,), TIME_DTYPE),
        t_window=T_WINDOW, w_max=7, wta_k=1, mu_capture=mu, mu_backoff=mu,
        mu_search=mu, stabilize=True, response="rnl", epochs=1,
        lowering="mosaic", t_blk=128, v_blk=32, stochastic=True,
        keys=spec((d, 2), jnp.int32), v0=spec((), jnp.int32),
    ).compile()
    _assert_mosaic(compiled)


def test_assign_padded_compiles_for_v5e(spec):
    compiled = fused_column.assign_padded.lower(
        *_padded_operands(spec), t_window=T_WINDOW, wta_k=1,
        response="rnl", lowering="mosaic", t_blk=128, w_max=7,
    ).compile()
    _assert_mosaic(compiled)


def test_fused_step_pallas_padded_compiles_for_v5e(spec):
    d = 4
    step = jax.jit(
        lambda w, t, ops: fused_column.fused_step_pallas_padded(
            w, t, ops, t_window=T_WINDOW, w_max=7, wta_k=1, stabilize=True,
        )
    )
    compiled = step.lower(
        spec((d, P_PAD, Q_PAD), F32),
        spec((d, P_PAD), F32),
        spec((d, fused_column.N_OPERANDS), F32),
    ).compile()
    _assert_mosaic(compiled)


def test_rnl_fire_pallas_compiles_for_v5e(spec):
    fire = jax.jit(
        lambda t, w: rnl_response.rnl_fire_pallas(
            t, w, threshold=557.0, t_max=T_WINDOW, w_max=7, interpret=False,
        )
    )
    # Lightning2's 637x2 column over a 16-volley batch
    compiled = fire.lower(
        spec((16, 637), TIME_DTYPE), spec((637, 2), F32)
    ).compile()
    _assert_mosaic(compiled)
