"""The whole run, with the timed path broken underneath, comes out not
correct: once for each fault a training cell can have (CPU, tiny)."""
import pytest

from runner import run_tiny


def _unchanged_step(monkeypatch):
    import repro.core.trainer as trainer
    from repro.optim.adam import global_norm

    def adam_update(cfg, params, grads, state, lr_scale=1.0, **_):
        return params, state, {"grad_norm": global_norm(grads),
                               "lr": 0.0 * global_norm(grads)}
    monkeypatch.setattr(trainer, "adam_update", adam_update)


def _half_batch(monkeypatch):
    import repro.core.trainer as trainer
    make = trainer._make_loss_fn

    def make_half(model, part, use_swap):
        loss_fn = make(model, part, use_swap)
        return lambda params, batch: loss_fn(
            params, {k: v[:v.shape[0] // 2] for k, v in batch.items()})
    monkeypatch.setattr(trainer, "_make_loss_fn", make_half)


def _recovery_skipped(monkeypatch):
    import repro.recovery.strategies as strategies
    monkeypatch.setattr(strategies, "recover_stage",
                        lambda params, *a, **k: params)


@pytest.mark.parametrize("fault,caught_by", [
    (_unchanged_step, "change_gap"),
    (_half_batch, "grad_gap"),
    (_recovery_skipped, "recovery_gap"),
], ids=["state_unchanged", "half_batch", "recovery_skipped"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, caught_by):
    fault(monkeypatch)
    result = run_tiny(monkeypatch, "granite-moe-3b.L4", "churn4", seed=77)
    assert not result["correct"]
    c = result["checks"][caught_by]
    assert c["value"] > c["limit"], result["checks"]
