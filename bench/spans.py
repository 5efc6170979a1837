"""The program's own spans and counters (``repro.obs``), as the per-layer
readers see them.

The program records them only while the JAX profiler runs, so in a
``--trace 1`` run they cover the traced window and nothing else.
``snapshot()`` is None for a program without the recorder: its readers
then report nothing.  Sums over spans that were not recorded read 0, and
so do ratios whose base is 0.
"""


def snapshot():
    try:
        from repro import obs
    except ImportError:  # a program from before the recorder
        return None
    return obs.snapshot()


def ratio(num: float, den: float) -> float:
    return num / den if den else 0.0
