#!/usr/bin/env python3
"""Readings of the control: the reference in bfloat16 in the program's place.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...] [--seconds <s>]

For each seed, the cell's check compares the reference computed in
bfloat16 (the precision below the float32 the configurations state) with
the reference in float32, by the same numbers ``bench/run.py`` compares
for the program, and prints them beside the cell's limits.  A sound limit
is one that this control fails.  A sweep cell needs no window (the
control trains the same sampled candidates); a serving cell first runs
the service for ``--seconds`` at its own load to get the window's
requests and re-fits.  Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def readings(workload: str, seed: int, seconds: float, *, platform: str = "tpu",
             spec: run.Spec | None = None) -> dict:
    """``{number: control reading}`` of one seed."""
    spec = spec or run.Spec()
    cell = spec.cell(workload)
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    kind = spec.kind(traffic["kind"])
    import jax.numpy as jnp

    run.devices(platform, int(cell["chips"]))
    run.program(spec)
    runner = kind.Cell(config, traffic, seed, int(cell["chips"]))
    runner.prepare_control(seconds)
    got = runner.reference_outputs(jnp.bfloat16)
    want = runner.reference_outputs(jnp.float32)
    return dict(runner.compare(got, want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    spec = run.Spec()
    limits = spec.traffic(spec.cell(args.workload)["traffic"])["limits"]
    for seed in args.seeds:
        got = readings(args.workload, seed, args.seconds, spec=spec)
        fails = any(v > limits[k] for k, v in got.items())
        print(json.dumps({"workload": args.workload, "seed": seed, "control": got,
                          "limits": limits, "control_fails": fails}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
