"""The harness end to end at a tiny size on the CPU, through
``harness.main``'s test-only arguments (never a flag of the command), and
the command's refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, run_tiny

SEED = 2 ** 31 + 7


@pytest.fixture(scope="module")
def untraced(tiny_root):
    """One untraced run of the tiny cell, shared by the tests below."""
    return run_tiny(tiny_root, seed=SEED)


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_runs_end_to_end(tiny_root, untraced, trace):
    rc, result, err = (untraced if not trace
                       else run_tiny(tiny_root, seed=SEED, trace=1))
    assert rc == 0, err
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    want = ({"seeds_per_s", "peak_hbm_gib", "setup_s"} if not trace else
            {"compile_s", "window_compiles", "device_idle_pct",
             "gen_device_ms", "gen_roofline", "cache_hit_pct",
             "distinct_request_pct", "model_device_ms", "mfu_pct"})
    assert want <= set(result["metrics"])
    if trace:
        assert result["metrics"]["window_compiles"]["value"] == 0
        assert 0 < result["metrics"]["gen_roofline"]["value"] <= 100
        assert result["device"]["busy_s"] > 0
        assert len(result["breakdown"]["device_ops"]) <= 10
        assert "collective_device_ms" not in result["metrics"]
    # the checked numbers are the last lines of standard error
    tail = err.strip().splitlines()[-len(result["checks"]):]
    assert all(line.startswith("check ") and " limit " in line
               for line in tail)


def test_same_seed_same_work(tiny_root, untraced):
    """A seed fixes the parameters, seed order and sampling: the checked
    numbers of two runs of one seed agree."""
    again = run_tiny(tiny_root, seed=SEED)[1]
    assert again["checks"] == untraced[1]["checks"]


def test_no_tpu_means_no_result(tiny_root):
    """Asked for a TPU on the CPU, the harness exits non-zero with a
    message and prints no result."""
    rc, result, err = run_tiny(tiny_root, seed=1, platform="tpu")
    assert rc != 0 and result is None and "no TPU" in err


def test_command_without_tpu_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "gcn-rmat23-w1", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's own
    files, the command fails and prints no result."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".data", ".traces",
                                                      "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, *bench["command"][1:],
                        "--workload", "gcn-rmat23-w1", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
