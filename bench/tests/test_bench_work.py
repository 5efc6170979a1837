"""Model work and peaks: the counts follow the algorithm's own shapes."""
import inspect

import numpy as np
import pytest

import benchkit  # noqa: F401  (puts bench/ on the path)
import work

TABLE2 = {  # (p, q): 2 * p * q * t_max fire operations per volley at t_max 64
    (65, 2): 16640, (96, 2): 24576, (152, 2): 38912, (343, 2): 87808,
    (637, 2): 163072, (470, 5): 300800, (270, 25): 864000,
}


@pytest.mark.parametrize("shape", sorted(TABLE2))
def test_fire_ops_pinned_for_table2(shape):
    p, q = shape
    assert work.fire_ops(p, q, 64) == TABLE2[shape]
    assert work.fit_ops(p, q, 64, 900) == 900 * (TABLE2[shape] + p * q)
    assert work.assign_ops(p, q, 64, 900) == 900 * TABLE2[shape]


def test_fire_ops_count_a_plain_loop():
    """One min and one add per synapse per cycle, counted in a plain loop."""
    p, q, t_max = 7, 3, 5
    ops = 0
    for _t in range(t_max):
        for _j in range(q):
            for _i in range(p):
                ops += 2  # min(ramp, w), then accumulate
    assert work.fire_ops(p, q, t_max) == ops


def test_padding_and_planes_do_not_enter():
    # the fused kernel works on a 384 x 32 x 128 tile for WordSynonyms and
    # issues w_max + 1 plane matmuls; the model count sees neither
    assert work.fire_ops(270, 25, 64) * 384 * 32 * 128 == work.fire_ops(384, 32, 128) * 270 * 25 * 64
    for fn in (work.fire_ops, work.stdp_ops, work.fit_ops, work.assign_ops,
               work.fit_bytes, work.assign_bytes):
        params = set(inspect.signature(fn).parameters)
        assert not params & {"p_pad", "q_pad", "t_window", "t_blk", "w_max", "planes"}


def test_bytes_are_the_least_traffic():
    assert work.fit_bytes(270, 25, 900) == 900 * 270 + 2 * 270 * 25 * 4
    assert work.assign_bytes(270, 25, 900) == 900 * (270 + 4) + 270 * 25 * 4


def test_peaks_of_the_v5e_and_unknown_device():
    v5e = work.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


def test_roofline_share_and_bound():
    peak = work.peaks("TPU v5 lite")
    share, bound = work.roofline_pct(197e12, 1.0, 2.0, peak)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = work.roofline_pct(1.0, 819e9, 4.0, peak)
    assert bound == "memory" and share == pytest.approx(25.0)
    assert work.roofline_pct(1.0, 1.0, 0.0, peak)[0] is None
    assert np.isfinite(work.roofline_pct(864000, 2000, 1e-6, peak)[0])
