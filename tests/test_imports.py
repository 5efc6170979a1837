"""The package tree imports cleanly, loads nothing outside itself, and its
launcher runs.

  * every module and package under ``src/repro`` imports on its own, so a
    stale import anywhere in the tree fails its own case;
  * ``import repro.serve, repro.dse`` and every package, in a fresh
    interpreter, load only modules of the TNN system — no eager package
    ``__init__`` import pulls in modules that the system does not call;
  * ``python -m repro.launch.serve_tnn --smoke`` serves, drains on its own
    SIGTERM and exits 0 with nothing dropped.
"""
import importlib
import json
import os
import pathlib
import pkgutil
import subprocess
import sys

import pytest

import repro

SRC = pathlib.Path(list(repro.__path__)[0]).resolve().parent

_WALK = list(pkgutil.walk_packages(repro.__path__, "repro."))
MODULES = ["repro"] + sorted(m.name for m in _WALK)
PACKAGES = sorted(m.name for m in _WALK if m.ispkg)

# Packages of which the TNN system calls only these modules: any other
# module loaded under them was pulled in by an eager import.
KEPT_ONLY = {
    "repro.configs": {"tnn_columns"},
    "repro.data": {"ucr"},
    "repro.distributed": {"checkpoint", "straggler"},
    "repro.launch": {"serve_tnn"},
    "repro.roofline": {"costmodel"},
}


def _run(args, timeout):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            p for p in (str(SRC), os.environ.get("PYTHONPATH")) if p
        ),
    )
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env,
        cwd=str(SRC.parent), timeout=timeout,
    )


@pytest.mark.parametrize("name", MODULES)
def test_module_imports(name):
    assert importlib.import_module(name).__name__ == name


def test_service_and_dse_load_only_the_kept_tree():
    """The service, the DSE and every package ``__init__``, imported in a
    fresh interpreter, load no module outside the kept tree."""
    r = _run(
        ["-c", "import importlib, json, sys\n"
               f"for m in {['repro.serve', 'repro.dse'] + PACKAGES!r}:\n"
               "    importlib.import_module(m)\n"
               "print(json.dumps(sorted(m for m in sys.modules "
               "if m == 'repro' or m.startswith('repro.'))))"],
        timeout=300,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    loaded = json.loads(r.stdout.strip().splitlines()[-1])
    assert {"repro.serve.service", "repro.dse.explore"} <= set(loaded)
    assert not set(loaded) - set(MODULES), set(loaded) - set(MODULES)
    stray = [
        m for m in loaded
        for pkg, kept in KEPT_ONLY.items()
        if m.startswith(pkg + ".") and m[len(pkg) + 1:].split(".")[0] not in kept
    ]
    assert not stray, stray


def test_serve_tnn_smoke_drains_cleanly():
    r = _run(["-m", "repro.launch.serve_tnn", "--smoke"], timeout=300)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert "drained cleanly" in r.stdout and "0 dropped" in r.stdout, r.stdout
