"""`correct` has to be able to come out false. At the rehearsal's size,
on the CPU, through the drivers themselves (the harness's look for a
chip skipped): the control — the reference one precision below what the
configuration states — fails a number, and so does each fault planted
under the timed path."""

import time

import pytest

import run as harness


def drive(workload, seed, seconds, **extra):
    bench = harness.load_benchmark(rehearse=True)
    ctx = harness.build_context(bench, workload, seed, seconds, False,
                                False, None, time.perf_counter(), **extra)
    driver = harness.load_module("drivers", ctx.traffic["driver"])
    return driver.run(ctx)


def failed(compared):
    return [k for k, (value, limit) in compared.items()
            if not value <= limit]


def test_train_sound_run_is_correct_and_control_is_not():
    out = drive("tiny.tiny-train", 7, 1.0, control=True)
    assert out["correct"], out["compared"]
    assert not out["control"]["correct"]
    assert failed(out["control"]["compared"]), out["control"]
    assert not out["half_batch_in_reference"]["correct"]
    assert "grad_norm_gap_worst_leaf" in failed(
        out["half_batch_in_reference"]["compared"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(fault):
    out = drive("tiny.tiny-train", 8, 1.0, fault=fault)
    assert not out["correct"]
    assert failed(out["compared"])


def test_serve_sound_run_is_correct_and_control_is_not():
    out = drive("tiny.tiny-chat", 9, 2.0, control=True)
    assert out["correct"], (out["compared"], out["facts"])
    assert not out["control"]["correct"]
    assert failed(out["control"]["compared"]), out["control"]


def test_serve_altered_token_is_not_correct():
    out = drive("tiny.tiny-chat", 10, 2.0, fault="token_altered")
    assert not out["correct"]
    assert failed(out["compared"])


def test_the_command_prints_the_verdicts(capsys):
    """`run.py --control --fault`: the result line carries the verdict
    on the control and names the fault; `correct` is false."""
    import json
    assert harness.main(["--workload", "tiny.tiny-train", "--seeds", "11",
                         "--seconds", "0.5", "--rehearse-cpu", "--control",
                         "--fault", "half_batch"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["fault"] == "half_batch" and line["correct"] is False
    assert line["control"]["correct"] is False
    assert list(line)[-1] == "compared"
