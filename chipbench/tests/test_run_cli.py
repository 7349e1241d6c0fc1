"""``run.py`` refuses to run, and prints no result line, where it cannot
measure."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "paper-n16384-closed", "--seed", "2147483660",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, extra_env):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "REPRO_FFT_BACKEND")}
    env.update(extra_env)
    return subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_exits_nonzero_without_a_tpu():
    proc = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    _no_result(proc)
    assert "no TPU" in proc.stderr


def test_exits_nonzero_with_the_reference_backend_forced():
    _no_result(_run(ROOT, {"JAX_PLATFORMS": "cpu",
                           "REPRO_FFT_BACKEND": "xla"}))


def test_exits_nonzero_for_an_unknown_cell():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                           "no-such-cell", "--seed", "1", "--seconds", "1"],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)
    _no_result(proc)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    _no_result(_run(tmp_path, {"JAX_PLATFORMS": "cpu"}))
