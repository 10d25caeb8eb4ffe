"""A whole run on the CPU at tiny sizes, with the chip guard skipped: the
sound program comes out correct, the planted faults and the control do not,
and the last line has the contract's shape."""
from __future__ import annotations

import json

import pytest

import chipbench_fixtures as fx

from benchmarks.chip import run

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    return fx.tiny_tree(tmp_path_factory.mktemp("chipbench"))


def _run(reg, cell, seed=11, **kw):
    return run.run_cell(cell, seed, 0.5, False, registry=reg,
                        require_chip=False, **kw)


def _line_shape(out, e2e):
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert set(out["metrics"]) == set(e2e)
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    for c in out["checks"].values():
        assert set(c) == {"value", "limit"}
    json.loads(json.dumps(out))


@pytest.mark.parametrize("cell", ["tiny.train"])
def test_sound_training_run_is_correct(reg, cell):
    out = _run(reg, cell, seed=2 ** 31 + 12345, control=True)
    _line_shape(out, ["train_tokens_per_s", "setup_s"])
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    # the control (float8 matmul inputs) and each planted fault, put in the
    # program's place, come out not correct by the run's own comparison
    assert set(out["control"]) == {"fp8", "half_batch", "no_exchange"}
    for name, c in out["control"].items():
        assert not c["correct"], (name, c["checks"])


def _unchanged_state(trainer):
    rec = trainer.segment_runner
    inner = rec.call
    rec.call = lambda p, o, b, lr: (p, o, inner(p, o, b, lr)[2])


def _half_batch(trainer):
    rec = trainer.segment_runner
    inner = rec.call

    def call(p, o, b, lr):
        half = b["tokens"].shape[2] // 2
        return inner(p, o, {k: v[:, :, :half] for k, v in b.items()}, lr)
    rec.call = call


def _no_exchange(trainer):
    """Deliveries update the global model, but no region receives it."""
    eng = trainer.engine
    deliver = eng._fns.deliver
    eng._fns = eng._fns._replace(
        deliver=lambda state, t, ps, p: (deliver(state, t, ps, p)[0], ps))


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _no_exchange],
                         ids=["state_unchanged", "half_batch", "no_exchange"])
def test_training_faults_are_not_correct(reg, fault):
    out = _run(reg, "tiny.train", break_program=fault)
    assert not out["correct"], out["checks"]


def test_no_chip_means_no_result(reg, capsys):
    with pytest.raises(run.device.NoChip):
        run.run_cell("tiny.train", 1, 0.5, False, registry=reg)
    assert not any(line.startswith("{")
                   for line in capsys.readouterr().out.splitlines())


def _bare_checkout(tmp):
    """BENCHMARK.json and the benchmark's own directory, nothing else."""
    import shutil
    shutil.copy(fx.ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(fx.ROOT / "benchmarks" / "chip",
                    tmp / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp


@pytest.mark.parametrize("where", ["checkout", "bare"])
def test_the_command_fails_without_a_chip_or_the_program(tmp_path, where):
    import os
    import subprocess
    import sys
    root = fx.ROOT if where == "checkout" else _bare_checkout(tmp_path)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")}
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "paper_150m.train.cocodc", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
