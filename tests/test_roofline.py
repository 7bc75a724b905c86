"""Roofline tripwires: a measurement that cannot prove it exercised the
MXU must never become an MFU denominator.

The r05 retraction (docs/PERFORMANCE.md) is the motivating failure: XLA's
algebraic simplifier rewrote a splat-operand matmul into an O(n^2) column
reduction and the "641 TF/s on a 197 TF/s chip" number was briefly
published.  These tests pin the three tripwires structurally on CPU —
the CPU compiler does not reproduce the TPU fold, so the rejected-operand
cases feed the checker the folded artifacts directly.
"""
import json
import subprocess
import sys
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
from tools import roofline  # noqa: E402


def test_real_matmul_hlo_accepted():
    a = roofline._row_stochastic(64)
    f = jax.jit(lambda x: x @ x)
    hlo = f.lower(a).compile().as_text()
    roofline.assert_real_dot(hlo)          # must not raise


def test_dot_free_hlo_rejected():
    """A compiled module where the dot was folded away (what the TPU
    simplifier produced from the splat operand) must be rejected before
    it is ever timed."""
    # a real compiled module with NO dot in it: elementwise + reduce —
    # exactly the shape of the splat rewrite (scale + column reduction)
    f = jax.jit(lambda x: (x * 0.125).sum(axis=0, keepdims=True) + x * 0.0)
    hlo = f.lower(jnp.ones((64, 64), jnp.float32)).compile().as_text()
    with pytest.raises(roofline.RooflineError, match="folded"):
        roofline.assert_real_dot(hlo)


def test_empty_hlo_rejected():
    with pytest.raises(roofline.RooflineError):
        roofline.assert_real_dot("")


def test_rate_above_spec_peak_rejected():
    with pytest.raises(roofline.RooflineError, match="exceeds"):
        roofline.check_rate_bound(641e12, 197e12)   # the r05 artifact


def test_rate_under_peak_accepted():
    roofline.check_rate_bound(150e12, 197e12)
    roofline.check_rate_bound(1e9, None)            # unknown device: no bound


def test_nonpositive_rate_rejected():
    with pytest.raises(roofline.RooflineError):
        roofline.check_rate_bound(0.0, 197e12)


def test_scaling_tripwire_demotes_flat_curve():
    """time(2n) ~= time(n) means the probe never scaled O(n^3): both rows
    lose trusted status even though each rate sits under the peak."""
    rows = [
        {"probe": "mxu_bf16_4096", "n": 4096, "ms": 10.0, "trusted": True,
         "suspect": False},
        {"probe": "mxu_bf16_8192", "n": 8192, "ms": 10.4, "trusted": True,
         "suspect": False},
    ]
    roofline.apply_scaling_tripwire(rows)
    assert all(r["suspect"] and not r["trusted"] for r in rows)
    assert "scaling tripwire" in rows[0]["note"]


def test_scaling_tripwire_keeps_cubic_curve():
    rows = [
        {"probe": "mxu_bf16_4096", "n": 4096, "ms": 10.0, "trusted": True,
         "suspect": False},
        {"probe": "mxu_bf16_8192", "n": 8192, "ms": 78.0, "trusted": True,
         "suspect": False},
    ]
    roofline.apply_scaling_tripwire(rows)
    assert all(r["trusted"] and not r["suspect"] for r in rows)


def test_smoke_run_produces_trusted_probe():
    """The in-process smoke calibration yields a trusted MXU row (the
    structural tripwire passed on a real compiled matmul) and an HBM row
    with the dispatch-corrected number."""
    doc = roofline.run(smoke=True)
    assert doc["ok"] and doc["platform"] == "cpu"
    assert any(r["trusted"] for r in doc["mxu"])
    assert all("flops_per_sec" in r for r in doc["mxu"] if r["trusted"])
    hbm = doc["hbm"][0]
    assert hbm["dispatch_corrected_gbps"] > 0
    assert hbm["gbps"] > 0


@pytest.mark.slow
def test_smoke_cli_writes_artifact(tmp_path):
    out = tmp_path / "roofline_test.json"
    p = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                      "roofline.py"), "--smoke", "--out", str(out)],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr
    doc = json.loads(out.read_text())
    assert doc["ok"] and doc["mxu"]
    # stdout carries the same single-line document (battery capture path)
    assert json.loads(p.stdout.strip().splitlines()[-1])["ok"]


def test_row_stochastic_operand():
    a = np.asarray(roofline._row_stochastic(32), np.float32)
    np.testing.assert_allclose(a.sum(axis=1), 1.0, atol=5e-2)  # bf16 rounding
    assert a.std() > 0                      # random, not a splat
