"""Traffic kind ``sweep_rule``: the ``sweep`` kind's campaigns under the
configuration's own STDP rule.

The ``sweep`` kind builds its design space from the grid alone, so every
candidate learns with the program's default (expected-mode) rule.  This
kind passes the configuration's ``stdp`` block into the
``DesignSpace`` (its ``stdp`` field), so a configuration that states
stochastic STDP sweeps stochastic designs, and checks them against
``bench/reference_stochastic.py``.  Everything else — traffic keys, the
window, the numbers and the comparison (``weight_gap``,
``rand_index_gap``) — is the ``sweep`` kind's.
"""
from __future__ import annotations

import os
import sys

import numpy as np

import reference
import reference_stochastic


def _sweep_kind():
    """``kinds/sweep.py``, loaded by its path as the harness loads kinds
    (only ``bench/`` is on the import path)."""
    import importlib.util

    name = "kinds.sweep"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "sweep.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


sweep = _sweep_kind()


class Cell(sweep.Cell):
    """A sweep cell whose designs learn with the configuration's rule."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        super().__init__(config, traffic, seed, chips)
        self.stochastic = config["stdp"]["mode"] == "stochastic"

    def _sweep(self, explore_seed: int):
        import jax
        from repro.core.types import STDPConfig
        from repro.dse.explore import explore
        from repro.dse.space import DesignSpace

        rule = STDPConfig(**self.config["stdp"])
        out = []
        with jax.profiler.TraceAnnotation("bench.explore"):
            for d in self.designs:
                space = DesignSpace(q=d.q_list, t_max=d.t_max,
                                    threshold_scale=d.scales, stdp=rule)
                out.append(explore(d.x, d.y, space, epochs=self.epochs, seed=explore_seed))
        return out

    def reference_outputs(self, dtype):
        """The reference's (weights, Rand index) of each sampled candidate,
        by the configuration's rule."""
        if not self.stochastic:
            return super().reference_outputs(dtype)
        import jax.numpy as jnp

        stats = reference.statics(self.config)
        out = []
        for si, di, ci in self._sample:
            d = self.designs[di]
            q, t, scale = d.candidates()[ci]
            seed = self.sweeps[si][0]
            thr = scale * reference.suggested_threshold(d.p, self.w_max)
            xs = reference.encode(d.x, t)
            w0 = reference_stochastic.explore_init(seed, ci, d.p, q, self.w_max)
            key = reference_stochastic.explore_stream_key(seed, ci)
            w = reference_stochastic.fit(w0, xs, jnp.float32(thr), key, t_max=t,
                                         epochs=self.epochs, statics=stats, dtype=dtype)
            ids = reference.assign(w, xs, jnp.float32(thr), t_max=t, dtype=dtype)
            out.append((np.asarray(w, np.float32),
                        reference.rand_index(d.y, np.asarray(ids))))
        return out
