"""bench/run.py refuses to run without a TPU, and without the program;
on the CPU at tiny sizes (the chip check skipped) a sound run comes out
correct, and runs with the timed path broken underneath do not."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import manifest
import run as bench_run
import tiny

CELLS = tiny.NAMES


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "vgg16bn-mod.bkfac",
         "--seed", "3", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _last_line_is_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "correct" in json.loads(lines[-1])
    except ValueError:
        return False


def test_refuses_the_cpu():
    p = _cli(manifest.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not _last_line_is_result(p.stdout)


def test_refuses_without_the_program(tmp_path):
    shutil.copy(manifest.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(manifest.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    assert not _last_line_is_result(p.stdout)


def _run(cell, seed=5):
    return bench_run.run_cell(cell.name, seed, 0.0, False,
                              require_tpu=False, cell=cell)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    cell = tiny.tiny_cell(name)
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


def _stale_kfac(real):
    def make(loss_fn, opt, n_tokens, **kw):
        step = real(loss_fn, opt, n_tokens, **kw)

        def stale(state, batch, work, landing=None, mbuf=None, cstate=None):
            new, loss = step(state, batch, work, landing)
            return new._replace(params=state.params), loss
        return stale
    return make


def _wrap_loss(config, transform):
    """A build_model whose loss sees ``transform(batch)``."""
    real = manifest.config_module(config).build_model

    def build(sizes, n_stat):
        init, loss_fn, taps = real(sizes, n_stat)

        def broken(params, probes, batch):
            return loss_fn(params, probes, transform(batch))
        return init, broken, taps
    return build


def _half(batch):
    x, y = batch
    return x[: x.shape[0] // 2], y[: y.shape[0] // 2]


def _label(batch):
    """One image's label changed to the next class where the batch
    reaches the loss."""
    x, y = batch
    return x, y.at[0].set((y[0] + 1) % 10)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["stale", "half", "label"])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    from repro.train import loop
    cell = tiny.tiny_cell(name)
    adapter = manifest.config_module(cell.config)
    if fault == "stale":
        monkeypatch.setattr(loop, "make_scheduled_kfac_step",
                            _stale_kfac(loop.make_scheduled_kfac_step))
    else:
        monkeypatch.setattr(adapter, "build_model", _wrap_loss(
            cell.config, _half if fault == "half" else _label))
    out = _run(cell)
    assert not out["correct"], out["checks"]
