"""Tests for the analytic MXU-geometry roofline (benchmarks/impala_roofline.py).

The analytic ceiling is what a measured learner MFU is held against, so its
arithmetic is pinned here: layer inventory, the
narrow-channel lane-occupancy caps, and the cross-check against XLA's own
cost analysis of the exact benchmarked step.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks"))

from impala_roofline import analytic_mxu_ceiling  # noqa: E402


def test_reference_geometry_ceiling():
    out = analytic_mxu_ceiling()
    # 3 section convs + 3*4 residual convs + fc + 2 heads = 18 layers.
    assert len(out["layers"]) == 18
    # The published explanation: ceiling ~0.148 at the reference shape.
    assert 0.14 < out["weighted_mxu_ceiling"] < 0.16
    # Every conv is lane-capped at C_out/128.
    for l in out["layers"]:
        if l["layer"].startswith("conv"):
            c_out = int(l["layer"].split("->")[1])
            assert l["mxu_util_ceiling"] <= c_out / 128 + 1e-9


def test_wide_model_ceiling_approaches_one():
    # The falsifiable prediction: widening channels to MXU width lifts the
    # ceiling to ~1 — MFU should rise with width on chip.
    wide = analytic_mxu_ceiling(channels=(64, 128, 128))
    assert wide["weighted_mxu_ceiling"] > 0.75
    assert wide["weighted_mxu_ceiling"] > 4 * analytic_mxu_ceiling()["weighted_mxu_ceiling"]


def test_flop_shares_sum_to_one():
    out = analytic_mxu_ceiling()
    assert abs(sum(l["flop_share"] for l in out["layers"]) - 1.0) < 0.01


@pytest.mark.slow
def test_xla_cost_analysis_corroborates():
    # XLA's counted FLOPs for the exact benchmarked fwd+bwd step should be
    # ~3x the analytic forward pass (the stated approximation).
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import bench

    step, params, opt_state, batch = bench.build_step()
    cost = step.lower(params, opt_state, batch).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    if not flops:
        pytest.skip("cost analysis unavailable on this backend")
    fwd = analytic_mxu_ceiling()["forward_gflops"] * 1e9
    assert 2.5 < flops / fwd < 3.5
