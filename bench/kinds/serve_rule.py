"""Traffic kind ``serve_rule``: the ``serve`` kind's open-loop service
under the configuration's own STDP rule.

The ``serve`` kind starts every design from uniform float weights and
checks the window against the expected-mode reference, so it can only
serve expected STDP.  This kind starts each design from integer counters
``rng.integers(0, w_max + 1)`` (what a column that learns by unit steps
holds), passes the configuration's ``stdp`` block to the service as the
``serve`` kind does, and, when that block states stochastic STDP, checks
answers and counters against ``bench/reference_stochastic_serve.py``.
Everything else — traffic keys, the schedule, the window, the numbers and
the comparison (``answer_mismatch``, ``weight_gap``) — is the ``serve``
kind's.
"""
from __future__ import annotations

import os
import sys

import numpy as np

import reference
import reference_stochastic_serve
import work


def _serve_kind():
    """``kinds/serve.py``, loaded by its path as the harness loads kinds
    (only ``bench/`` is on the import path)."""
    import importlib.util

    name = "kinds.serve"
    if name not in sys.modules:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


serve = _serve_kind()


class Cell(serve.Cell):
    """A serving cell whose designs learn with the configuration's rule
    from integer counters."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        super().__init__(config, traffic, seed, chips)
        self.stochastic = config["stdp"]["mode"] == "stochastic"
        rng = np.random.default_rng([self.seed, 2])
        self.w0 = {
            n: rng.integers(0, self.w_max + 1, (d["p"], d["q"])).astype(np.float32)
            for n, d in self.designs.items()
        }

    def layer_context(self) -> dict:
        """The ``serve`` kind's context, plus what the replay counted of
        the window's re-fits: the live design-volleys times epochs
        (``refit_design_volleys``, what the program counts under the same
        name) and the least bytes (``refit_bytes``): per committed re-fit
        of a design, its live volleys read once and its weights read and
        written once (``work.fit_bytes``)."""
        ctx = super().layer_context()
        ctx["refit_design_volleys"] = self.traffic["refit_epochs"] * sum(
            self._replayed.trained.values())
        ctx["refit_bytes"] = sum(
            work.fit_bytes(self.designs[n]["p"], self.designs[n]["q"], 0)
            * (len(self._replayed.versions[n]) - 1)
            + self._replayed.trained[n] * self.designs[n]["p"] * work.TIME_BYTES
            for n in self.designs
        )
        return ctx

    def replay(self, dtype):
        """The reference service replayed over the window's events, by the
        configuration's rule."""
        sim, encodes = self._replay_rule(dtype)
        self._replayed = sim
        return sim, encodes

    def _replay_rule(self, dtype):
        if not self.stochastic:
            return super().replay(dtype)
        names = list(self.designs)
        encodes = {
            nm: reference.encode(self.streams[nm], self.designs[nm]["t_max"]) for nm in names
        }
        t = self.traffic
        sim = reference_stochastic_serve.ServiceReplay(
            {nm: (d["p"], d["q"], d["t_max"], self.threshold(nm))
             for nm, d in self.designs.items()},
            self.w0, encodes, seed=self.seed % (2**31 - 1),
            batch_size=t["batch_size"], refit_every=t["refit_every"],
            refit_window=t["refit_window"], refit_epochs=t["refit_epochs"],
            statics=reference.statics(self.config), dtype=dtype,
        )
        for ev in self.events:
            if ev is None:
                sim.flush()
            else:
                sim.submit(*ev)
        sim.flush()
        return sim, encodes
