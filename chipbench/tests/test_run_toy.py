"""The harness end to end at a toy size on the CPU: the flow of a run, the
comparison that decides ``correct`` (sound, broken underneath, and in the
lower-precision control), and the refusal to measure off the chip."""

import json
import os
import subprocess
import sys
import time

import pytest

from conftest import ROOT, TOY


def _run(workload, seed=11, seconds=2.0, trace=False):
    from chipbench import harness

    lines = []
    rc = harness.run_cell(workload, seed, seconds, trace,
                          t0=time.monotonic(), root=TOY, need_chip=False,
                          out=lines.append)
    assert rc == 0
    return json.loads(lines[-1])


@pytest.mark.parametrize("workload", ["m7b-serve-chat", "m7b-serve-longdoc",
                                      "m7b-train-1chip"])
def test_toy_cell_runs_and_is_correct_but_prints_no_device_metric(workload):
    line = _run(workload, seed=2**31 + 5)
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}      # a CPU time is never a device metric


def test_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from horovod_tpu.serving import engine as E

    real = E.InferenceEngine._emit

    def emit(self, slot, tok):
        return real(self, slot, (tok + 1) % self.cfg.vocab_size)

    monkeypatch.setattr(E.InferenceEngine, "_emit", emit)
    assert _run("m7b-serve-chat")["correct"] is False


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import optax

    monkeypatch.setattr(
        optax, "apply_updates", lambda params, updates: params)
    assert _run("m7b-train-1chip")["correct"] is False


def test_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    from horovod_tpu.models import transformer as T

    real = T.loss_fn

    def half(params, batch, cfg):
        return real(params, {k: v[:1] for k, v in batch.items()}, cfg)

    monkeypatch.setattr(T, "loss_fn", half)
    assert _run("m7b-train-1chip")["correct"] is False


def test_off_chip_run_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "m7b-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_four_virtual_devices_run_the_data_parallel_cell():
    """The dp4 cell's path (hvd mesh of four, rows split over the chips,
    the reference's own row split) on four virtual CPU devices."""
    code = (
        "import sys, time, json; sys.path.insert(0, %r)\n"
        "from chipbench import harness\n"
        "lines = []\n"
        "harness.run_cell('m7b-train-dp4', 9, 1.0, False, "
        "t0=time.monotonic(), root=%r, need_chip=False, out=lines.append)\n"
        "print(lines[-1])\n" % (ROOT, TOY))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
