"""Whole runs: the command refuses to run off the chip, and a run driven
in process on the CPU (past the chip check) decides ``correct`` by the
comparison, which a broken timed path fails."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import peaks, run, spec
from chipbench.server_child import ServerSide

BENCH = spec.load_benchmark()


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "vgg16-224.bulk",
         "--seed", str(2 ** 33 + 1), "--seconds", "2", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    return p


def test_no_tpu_exits_nonzero_without_a_result_line():
    p = _cli(spec.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(spec.ROOT / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "not beside the benchmark" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def tiny():
    cell = spec.workload(BENCH, "resnet18-cifar32.bulk")
    cfg = dict(spec.config(BENCH, cell["config"]), width_mult=0.0625)
    mix = dict(spec.traffic(cell["traffic"]), clients=2, buckets=[8],
               sizes={"min": 4, "max": 4}, bodies_per_size=2)
    return cell, cfg, mix


def _altered(out, x, cfg, params):
    return out.at[0, 0].add(1e-3 * abs(float(out[0, 0])) + 1e-3)


def _half_left_out(out, x, cfg, params):
    # every second row of the batch is left out, so a batch that is only
    # partly filled loses real rows too
    return out.at[1::2].set(0.0)


def _bf16x3_control(out, x, cfg, params):
    model = spec.model_module(cfg["family"])
    return model.forward(params, x, cfg, "bf16x3")


@pytest.mark.parametrize("fault", [None, _altered, _half_left_out,
                                   _bf16x3_control],
                         ids=["sound", "answer-altered", "half-batch-left-out",
                              "bf16x3-control"])
def test_in_process_run_and_its_faults(fault, monkeypatch):
    from repro.core.engine import CompiledNetwork
    cell, cfg, mix = tiny()
    if fault is not None:
        call = CompiledNetwork.__call__

        def broken(self, params, x):
            return fault(call(self, params, x), x, cfg, params)
        monkeypatch.setattr(CompiledNetwork, "__call__", broken)
    seed = 2 ** 32 + 11
    side = ServerSide(cfg, mix, seed, require_tpu=False)
    res = run.run_cell(side, BENCH, cell, cfg, mix, seed, 1.0, False,
                       t_start=0.0)
    assert res["attempted"] > 0
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"
    assert res["checks"]["failed"] == {"value": 0, "limit": 0}
    if fault is None:
        assert set(res["metrics"]) == {"images_per_s", "setup_s"}
        assert res["run"]["compiles_in_window"] == 0
        json.dumps(res)


def test_traced_in_process_run_reports_its_layer_metrics(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"flops_per_s": 1e12,
                                             "hbm_bytes_per_s": 1e11})
    cell, cfg, mix = tiny()
    side = ServerSide(cfg, mix, 5, trace=True, require_tpu=False)
    res = run.run_cell(side, BENCH, cell, cfg, mix, 5, 1.0, True,
                       t_start=0.0)
    assert res["correct"]
    assert "engine_host_ms_per_batch" in res["metrics"]
    assert res["metrics"]["mfu"]["unit"] == "%"
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
