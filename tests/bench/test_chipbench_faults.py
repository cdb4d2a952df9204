"""A run with the timed path broken underneath reads ``correct`` false:
each fault the training cells can have, planted in the program."""

import glob
import os

import numpy as np
import pytest

from repro.checkpoint import manager as manager_mod
from repro.train import trainer as trainer_mod


def _unchanged(orig):
    def make(model, opt_cfg, grad_accum=1):
        step = orig(model, opt_cfg, grad_accum)

        def fake(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return fake
    return make


def _half_batch(orig):
    def make(model, opt_cfg, grad_accum=1):
        step = orig(model, opt_cfg, grad_accum)

        def fake(params, opt_state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, half)
        return fake
    return make


@pytest.mark.parametrize("cell", ["ckpt-save", "ckpt-resume"])
@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_step_faults(run_cell, monkeypatch, cell, fault):
    monkeypatch.setattr(trainer_mod, "make_train_step",
                        fault(trainer_mod.make_train_step))
    rc, result, err = run_cell(cell, seed=21)
    assert rc == 0, err
    assert result["correct"] is False
    failed = [n for n, c in result["checks"].items()
              if not c["value"] <= c["limit"]]
    assert set(failed) <= {"loss_gap", "grad_gap", "change_gap",
                                 "change_mean_gap"}, failed


def test_saved_byte_altered(run_cell, monkeypatch):
    orig = manager_mod.CheckpointManager.save

    def save(self, step, tree, *a, **kw):
        st = orig(self, step, tree, *a, **kw)
        path = sorted(glob.glob(os.path.join(self.step_dir(step),
                                             "data_*.bin")))[0]
        with open(path, "r+b") as f:
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0x01]))
        return st

    monkeypatch.setattr(manager_mod.CheckpointManager, "save", save)
    rc, result, err = run_cell("ckpt-save", seed=22)
    assert rc == 0, err
    assert result["correct"] is False
    assert result["checks"]["saved_leaves_differing"]["value"] >= 1


def test_restored_value_altered(run_cell, monkeypatch):
    orig = manager_mod.CheckpointManager.restore

    def restore(self, *a, **kw):
        tree, st = orig(self, *a, **kw)
        leaf = np.asarray(tree["params"]["final_norm"])
        leaf.reshape(-1)[0] += 1.0
        return tree, st

    monkeypatch.setattr(manager_mod.CheckpointManager, "restore", restore)
    rc, result, err = run_cell("ckpt-resume", seed=23)
    assert rc == 0, err
    assert result["correct"] is False
    assert result["checks"]["restored_leaves_differing"]["value"] >= 1
