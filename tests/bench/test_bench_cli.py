"""The command never measures without a chip: on the CPU, and in a
directory holding only the benchmark's files, it exits non-zero and prints
no result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "smollm-135m-w4a8.chat", "--seed", "3",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return subprocess.run([sys.executable, *bench["command"][1:], *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_no_tpu_no_result():
    _no_result(_run(ROOT))


def test_benchmark_files_alone_give_no_result(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
