"""``correct`` must be able to come out false. Two controls at a size the CPU
holds (the chip readings at the cells' own sizes are in PERF.md):

- the plain reference computed in int8, put in the program's place, fails the
  limits that the sound engine passes;
- a whole run, with the harness's look for a chip skipped, and the timed path
  broken underneath (a served token altered where it is produced; a training
  step that returns its state unchanged), prints ``"correct": false``.
"""

import json


import run as bench_run
import serving
import spec
from common import CompileCounter, Context


def _ctx(cellname, seed, seconds):
    import time

    return Context(cell=spec.load_cell(cellname), seed=seed, seconds=seconds, trace=False,
                   on_cpu=True, device={"platform": "cpu", "kind": "cpu", "count": 1},
                   t_process=time.perf_counter(), trace_dir="", counter=CompileCounter())


def test_the_int8_control_fails_the_limit_the_sound_engine_passes():
    ctx = _ctx("test-serve", 5, 6.0)
    kind = spec.load_module("traffic", "kinds", ctx.cell.kind + ".py")
    r = serving.readings(ctx, kind)
    limit = ctx.cell.workload["check"]["limits"]["gap_mean"]
    assert r["sound"]["served_tokens"] >= 400
    assert r["sound"]["gap_mean"] <= limit < r["control"]["gap_mean"], r
    assert r["sound"]["gap_max"] <= ctx.cell.workload["check"]["limits"]["gap_max"]


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_sound_serving_rehearsal_is_correct_and_reports_no_device_metric(capsys):
    assert bench_run.main(["--workload", "rehearsal-batch", "--seed", "3000000007",
                           "--seconds", "3", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {} and out["device"]["platform"] == "cpu" and out["rehearsal"] is True


def test_an_altered_token_makes_a_serving_run_incorrect(capsys, monkeypatch):
    from datatunerx_tpu.serving import batched_engine

    real_push = batched_engine.Request.push

    def push(self, token):  # the fault: every token leaves the engine one id too high
        real_push(self, (int(token) + 1) % 3000)

    monkeypatch.setattr(batched_engine.Request, "push", push)
    assert bench_run.main(["--workload", "rehearsal-batch", "--seed", "21",
                           "--seconds", "3", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False and out["failed"] == 0


def test_a_step_that_returns_its_state_unchanged_makes_a_training_run_incorrect(capsys, monkeypatch):
    from datatunerx_tpu.training import train_lib

    import jax
    import jax.numpy as jnp

    real_step = train_lib.Trainer.train_step
    calls = {"n": 0}

    def train_step(self, state, batch):
        kept = jax.tree_util.tree_map(jnp.copy, state.lora)  # the real step donates its state
        new_state, metrics = real_step(self, state, batch)
        calls["n"] += 1
        # the fault: from the second step on the update is dropped (the first one
        # still feeds Adam's moment, so only the parameters' change can show it)
        return (new_state if calls["n"] == 1 else new_state.replace(lora=kept)), metrics

    monkeypatch.setattr(train_lib.Trainer, "train_step", train_step)
    assert bench_run.main(["--workload", "rehearsal-train", "--seed", "4",
                           "--seconds", "1", "--trace", "0"]) == 0
    out = _last_line(capsys)
    assert out["correct"] is False


def test_a_sound_training_rehearsal_is_correct(capsys):
    assert bench_run.main(["--workload", "rehearsal-train", "--seed", "3000000011",
                           "--seconds", "1", "--trace", "0"]) == 0
    assert _last_line(capsys)["correct"] is True


def test_a_chip_cell_refuses_the_cpu():
    assert bench_run.main(["--workload", "qwen-serve-steady", "--seed", "1",
                           "--seconds", "1", "--trace", "0"]) == 3
