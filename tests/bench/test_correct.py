"""A sound run of the timed path comes out correct, and the reference's
lower-precision control does not (CPU, tiny sizes)."""
import math

import pytest

from bench import check
from runner import run_tiny
from tiny import TINY_LIMITS, tiny_config


@pytest.mark.parametrize("config,mix", [
    ("granite-moe-3b.L4", "churn4"),
    ("mamba2-1.3b.L8", "churn4"),
    ("granite-moe-3b.L4", "steady"),
])
def test_sound_run_is_correct(monkeypatch, config, mix):
    result = run_tiny(monkeypatch, config, mix, seed=2 ** 31 + 3)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert {"tokens_per_s", "setup_s"} <= set(result["metrics"])
    assert ("recover_ms" in result["metrics"]) == (mix == "churn4")
    if mix == "churn4":
        assert result["checks"]["untouched_moved"]["value"] == 0.0
        assert result["checks"]["lost_moments"]["value"] == 0.0


@pytest.mark.parametrize("config", ["granite-moe-3b.L4", "mamba2-1.3b.L8"])
def test_lower_precision_control_is_not_correct(config):
    """The reference itself, in the program's place with its operands one
    precision below the configuration's (bfloat16 under these float32
    tiny configurations; float8 under the cells' bfloat16), fails at
    least one limit."""
    cfg = tiny_config(config)
    ref = check.reference_capture(cfg, seed=5, k0=4)
    control = check.reference_capture(cfg, seed=5, k0=4,
                                      precision="bfloat16")
    values = check.readings(control, ref)
    checks = check.verdict(values, {k: TINY_LIMITS[k] for k in values})
    assert not check.is_correct(checks), values
    assert all(math.isfinite(v) for v in values.values())
