"""The command refuses a device that is not a TPU, and a checkout that
holds only the benchmark, printing no result line."""
import os
import pathlib
import shutil
import subprocess
import sys

from benchmarks.chip import run

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_cli_refuses_a_non_tpu_device(capsys):
    rc = run.main(["--workload", "sift1m.uniform", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "TPU" in out.err


def test_cli_refuses_an_unknown_workload(capsys):
    rc = run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"])
    assert rc != 0 and capsys.readouterr().out == ""


def test_checkout_of_the_benchmark_alone_gives_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "sift1m.uniform", "--seed", "3",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
