"""Whole runs of each kind of cell at the program's smoke widths on the
CPU, the chip check skipped: a sound run is ``correct``, and a run whose
timed path is broken underneath is not, once for each fault the cell can
have (a step that returns its state unchanged; half of the batch left out,
the mean taken over the rest; a token altered where it is produced)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).parent))

import tiny  # noqa: E402


def _run(tmp_path, cell, fault=None):
    run_mod, kind, ctx = tiny.context(cell, 2**31 + 21, tmp_path)
    out = kind.run(ctx, fault=fault)
    checks = out["checks"]
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


@pytest.mark.parametrize("cell", ["xlstm_l12_d768.train.all",
                                  "xlstm_l12_d768.train.dormant",
                                  "qwen3_14b_l8.serve.all"])
def test_a_sound_run_is_correct(tmp_path, cell):
    correct, checks = _run(tmp_path, cell)
    assert correct, checks


def _state_unchanged(loop_lib):
    make = loop_lib.make_train_megastep

    def broken_make(*args, **kw):
        step = make(*args, **kw)

        def broken(mstate, batches, tstate):
            (_, outs), mstate = step(mstate, batches, tstate)
            return (tstate, outs), mstate

        broken.monitor = step.monitor
        return broken

    loop_lib.make_train_megastep = broken_make


class _HalfBatch:
    """The model with its loss taken over the first half of the rows."""

    def __init__(self, arch):
        self._arch = arch

    def __getattr__(self, name):
        return getattr(self._arch, name)

    def loss_fn(self, params, batch):
        half = batch["tokens"].shape[0] // 2
        return self._arch.loss_fn(params,
                                  {k: v[:half] for k, v in batch.items()})


def _half_batch(loop_lib):
    make = loop_lib.make_train_megastep
    loop_lib.make_train_megastep = \
        lambda arch, *a, **kw: make(_HalfBatch(arch), *a, **kw)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_training_step_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.train import loop as loop_lib

    monkeypatch.setattr(loop_lib, "make_train_megastep",
                        loop_lib.make_train_megastep)
    correct, checks = _run(tmp_path, "xlstm_l12_d768.train.all", fault)
    assert not correct, checks


def test_an_altered_token_is_not_correct(tmp_path, monkeypatch):
    from repro.serve import driver as driver_mod

    sample = driver_mod.DecodeDriver.sample

    def altered(self, logits, rng):
        return (sample(self, logits, rng) + 1) % logits.shape[-1]

    def fault(mod):
        monkeypatch.setattr(mod.DecodeDriver, "sample", altered)

    correct, checks = _run(tmp_path, "qwen3_14b_l8.serve.all", fault)
    assert not correct, checks
    assert checks["logit_gap"]["value"] > checks["logit_gap"]["limit"]
