"""The training cell, driven at the program's SMOKE size on the CPU, with
its device check skipped: a sound run is correct; a step that returns its
state unchanged, or one that leaves out half of the batch, is caught; so
is the fp8 control in the program's place."""
import pytest

from bench.tests import cells_smoke as cs


def test_sound_run_is_correct():
    out = cs.run_train(seed=4_000_000_123, seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["notes"]["compiles_in_window"] == 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def _patch_step(monkeypatch, make):
    from bench.cells import train
    real_init = train.Run.__init__

    def init(self, cell, seed):
        real_init(self, cell, seed)
        self.trainer.train_step = make(self.trainer.train_step)

    monkeypatch.setattr(train.Run, "__init__", init)


def test_state_left_unchanged_is_caught(monkeypatch):
    import jax
    import jax.numpy as jnp

    def make(step):
        def frozen(params, opt, batch):
            copy = lambda t: jax.tree.map(jnp.copy, t)
            _, _, m = step(copy(params), copy(opt), batch)
            return params, opt, m
        return frozen

    _patch_step(monkeypatch, make)
    out = cs.run_train(seed=4_000_000_123, seconds=0.5)
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_caught(monkeypatch):
    def make(step):
        def half(params, opt, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt, {k: v[:n] for k, v in batch.items()})
        return half

    _patch_step(monkeypatch, make)
    out = cs.run_train(seed=4_000_000_123, seconds=0.5)
    assert not out["correct"], out["checks"]


def test_fp8_control_fails_the_limits():
    import jax
    from bench import reference
    from bench.cells import train
    cell = cs.train_cell()
    r = train.Run(cell, 23)
    shapes = r.shapes
    del r
    ref = train.reference_run(cell, 23, shapes)
    low = train.reference_run(cell, 23, shapes, reference.FP8)
    low["first_grad"] = reference.leaf_norms(low["first_grad"])
    nums = train.compare(low, ref, cell.mix["optimizer"])
    assert any(nums[k] > cell.limits[k] for k in nums), nums


def test_calibration_holds_each_reading_to_the_limits(capsys):
    import json
    from bench.cells import train
    cell = cs.train_cell()
    train.calibrate(cell, [31], {31}, 0.0, [], cs.DEVICE)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    verdict = {x["kind"]: x["correct"] for x in lines}
    assert verdict == {"program": True, "control": False,
                       "half_batch": False}
    assert set(lines[0]["checks"]) == set(cell.limits)
