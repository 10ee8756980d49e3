"""The Mamba-2 mixer's named scopes in the lowered fused step, and the ``ssd_scan_ms`` reader on a synthetic scoped context."""
from types import SimpleNamespace

import pytest

from bench import devscope
from test_scoped import _op_names

SSM_SCOPES = ("ssm_in_proj", "ssm_conv", "ssd_scan", "ssm_out")


@pytest.mark.parametrize("scope", SSM_SCOPES)
def test_the_fused_ssm_step_carries_each_mixer_scope(scope):
    assert any(scope in devscope.scope_path(n)
               for n in _op_names("mamba2-1.3b.L8")), scope


def test_ssd_scan_ms_reads_the_scans_device_time_per_step():
    reader = __import__("bench.metrics.ssd_scan_ms", fromlist=["read"])
    ctx = SimpleNamespace(steps=4, scoped={"scope_busy_s": {
        "ssd_scan": 0.012, "adam": 0.02}})
    assert reader.read(ctx) == pytest.approx(3.0)
    assert reader.read(ctx) == devscope.scope_ms_per_step(ctx, "ssd_scan")


@pytest.mark.parametrize("ctx", [
    SimpleNamespace(trace=None, steps=12),
    SimpleNamespace(scoped=None, steps=12),
    SimpleNamespace(scoped={"scope_busy_s": {"adam": 0.02}}, steps=12),
    SimpleNamespace(scoped={"scope_busy_s": {"ssd_scan": 0.02}}, steps=0)])
def test_ssd_scan_ms_reads_nothing_without_the_scope(ctx):
    """A run with no scoped trace, or one in which no operation ran under
    ``ssd_scan`` (a transformer cell, or the harness's context today),
    gives no value and raises nothing."""
    reader = __import__("bench.metrics.ssd_scan_ms", fromlist=["read"])
    assert reader.read(ctx) is None
