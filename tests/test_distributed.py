"""Distributed runtime: checkpoint/restart and the straggler monitor."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.checkpoint import Checkpointer
from repro.distributed.straggler import RebalancePolicy, StepMonitor


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_latest():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones(4)}}
        ck.save(3, tree, blocking=True)
        ck.save(7, jax.tree.map(lambda x: x * 2, tree), blocking=True)
        assert ck.latest_step() == 7
        like = jax.tree.map(lambda x: jnp.zeros_like(x), tree)
        out, step = ck.restore(like)
        assert step == 7
        np.testing.assert_allclose(np.asarray(out["a"]), np.arange(6.0).reshape(2, 3) * 2)


def test_checkpoint_interrupted_save_invisible():
    """A .tmp directory (simulated mid-write preemption) must not be
    restorable; the previous complete step remains LATEST."""
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        tree = {"a": jnp.ones(3)}
        ck.save(1, tree, blocking=True)
        os.makedirs(os.path.join(d, "step_2.tmp"))  # torn write
        assert ck.latest_step() == 1
        out, step = ck.restore(jax.tree.map(jnp.zeros_like, tree))
        assert step == 1


def test_checkpoint_structure_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, {"a": jnp.ones(3)}, blocking=True)
        with pytest.raises(ValueError):
            ck.restore({"a": jnp.zeros(3), "b": jnp.zeros(2)})


# ------------------------------------------------------------- straggler
def test_straggler_monitor_flags_outliers():
    m = StepMonitor(window=20, threshold=2.0, warmup=3)
    for i in range(10):
        m.observe(i, 0.1)
    ev = m.observe(10, 0.5)
    assert ev is not None and ev.ratio > 2
    assert not m.should_rebalance(patience=3)
    m.observe(11, 0.5)
    m.observe(12, 0.55)
    assert m.should_rebalance(patience=3)


def test_rebalance_policy_conserves_batch():
    pol = RebalancePolicy(num_shards=4, shave=0.25)
    w = pol.apply(slow_shard=2)
    assert abs(sum(w) - 4.0) < 1e-9
    assert w[2] < 1.0 and all(x > 1.0 for i, x in enumerate(w) if i != 2)
