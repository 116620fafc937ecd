"""What decides ``correct``: the reference against the program in float32,
a sound toy run correct, the control outside the cells' limits, and each
planted fault seen as not correct by a whole run of the harness."""

import json
import os

import pytest

import compare
import run as harness
from toy import BENCH, TOY_MODEL, make

CELLS = ("gpt2-medium.train-b8", "gpt2-large.train-fsdp4-b32")


# A toy's leaves are small and its bfloat16 rounding coarse: on the CPU the
# sound toy reads grad1 up to 0.02 under fsdp (0.002 under dp) and dparam3
# 0.007, where the chip at the cells' own sizes reads 0.004 and 0.003. So the
# whole-run tests hold the toy to limits of its own, under which a sound run is
# correct and each planted fault is not; the control is held to the cells'.
TOY_LIMITS = {"loss2": 0.005, "loss3": 0.005, "grad1": 0.05, "dparam3": 0.02}


def toy_run(tmp_path, limits=TOY_LIMITS, **kw):
    bench, bench_dir = make(str(tmp_path), rows=8, limits=limits, **kw)
    return harness.run_cell(bench, "toy.train", seed=2**31 + 77, seconds=0.3, trace=False,
                            root=str(tmp_path), bench_dir=bench_dir, require_chip=False)


def test_reference_agrees_with_the_program_in_float32(tmp_path):
    """models/gpt.py, its loss, gradients, clip and AdamW against the plain
    reference over three steps, both in float32: rounding only."""
    run = toy_run(tmp_path, compute="float32",
                  limits={"loss1": 1e-6, "loss2": 1e-6, "loss3": 1e-6, "grad1": 1e-4, "dparam3": 1e-4})
    assert run["correct"], run["checks"]
    assert len(run["step_ends"]) >= 2 and run["failed"] == 0


@pytest.mark.parametrize("cell,chips", [(CELLS[0], 1), (CELLS[1], 4)])
def test_sound_run_is_correct(tmp_path, cell, chips):
    """The program as configured (bfloat16 compute) on the cell's own mesh
    and strategy; the trainer-loop readers find the program's own events."""
    run = toy_run(tmp_path, like=cell, chips=chips)
    assert run["correct"], run["checks"]
    read = lambda name: harness.load_module("metrics", name).read(run)  # noqa: E731
    assert 0.0 < read("host_ms_per_step.train") < 1e3 * run["step_ends"][-1]
    assert read("recompiles.train") == 0
    run["events"].append({"etype": "recompile", "step": 5, "compile_s": 1.0, "count": 2})
    assert read("recompiles.train") == 2


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell, seed):
    """The reference with int8 matmuls, put in the program's place."""
    runner = harness.load_module("runners", "train")
    with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
        wl = json.load(f)
    wl["traffic"]["rows"] = 8
    run = {"workload": wl, "model": TOY_MODEL, "seed": seed, "chips": 1,
           "optim": wl["optim"]}
    ok, checks = compare.judge(
        compare.readings(runner.follow(run, matmul="int8"), runner.follow(run)), wl["limits"])
    assert not ok, checks


def _break(monkeypatch, fault):
    """Plant ``fault`` under the runner's recorder: in the step the program
    builds."""
    import jax
    import jax.numpy as jnp

    from dtc_tpu.train import trainer
    from dtc_tpu.train.train_step import Batch

    real_create = trainer.create_train_step

    def create(*args, **kwargs):
        step = real_create(*args, **kwargs)

        def state_unchanged(state, batch, rng):
            _, loss = step(jax.tree.map(jnp.copy, state), batch, rng)
            return state, loss

        def rows_left_out(state, batch, rng, keep):
            n = batch.x.shape[0] // keep
            return step(state, Batch(x=batch.x[:n], y=batch.y[:n]), rng)

        return {
            "state_unchanged": state_unchanged,
            "half_of_the_batch": lambda s, b, r: rows_left_out(s, b, r, 2),
            "no_exchange_between_chips": lambda s, b, r: rows_left_out(s, b, r, 4),
        }[fault]

    monkeypatch.setattr(trainer, "create_train_step", create)


@pytest.mark.parametrize("cell,chips,fault", [
    (CELLS[0], 1, "state_unchanged"),
    (CELLS[0], 1, "half_of_the_batch"),
    (CELLS[1], 4, "state_unchanged"),
    (CELLS[1], 4, "half_of_the_batch"),
    # Without the gradients' exchange each chip would step on its own rows:
    # the step on one chip's share of every batch.
    (CELLS[1], 4, "no_exchange_between_chips"),
])
def test_fault_is_not_correct(tmp_path, monkeypatch, cell, chips, fault):
    _break(monkeypatch, fault)
    run = toy_run(tmp_path, like=cell, chips=chips)
    assert not run["correct"], run["checks"]
    if fault == "state_unchanged":
        assert run["checks"]["dparam3"]["value"] == pytest.approx(1.0)
