"""The harness refuses what it cannot measure: no TPU, an unknown device
kind, a checkout without the program."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CMD = ["--workload", "kd.r34-r18.b64", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py"] + CMD, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            pass
    return False


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(run.SetupError, match="no peaks"):
        run.peaks_for("TPU v99")
    assert run.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12


def test_every_cell_has_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for wl in bench["workloads"]:
        traffic = run.read_json("traffic", wl["traffic"] + ".json")
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           traffic["kind"] + ".py"))
        assert os.path.exists(os.path.join(BENCH, "limits",
                                           wl["name"] + ".json"))
    for m in bench["per_layer"]:
        assert run.metric_reader(m["name"]).read
