"""``bench/run.py`` end to end on the CPU at a four-client size, with the
look for a TPU steered in the test; and unsteered, the refusal to run."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}


def test_a_run_prints_one_result_line(rehearse):
    rc, lines = rehearse(trace=0)
    assert rc == 0
    result = json.loads(lines[-1])
    assert set(result) == REQUIRED | {"checks"}
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"round_s", "round_p95_s", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for check in result["checks"].values():
        assert check["value"] <= check["limit"]


def test_a_traced_run_prints_the_layer_metrics(rehearse):
    rc, lines = rehearse(trace=1)
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is True
    # No device plane in a CPU trace: the kernel's readers find nothing and
    # their metrics are left out, never reported as 0.
    assert set(result["metrics"]) == {"sample_ms", "round_mfu",
                                      "compiles_per_round", "device_idle_share"}
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(result["breakdown"]["idle_gaps"]) <= 10


def test_the_same_seed_gives_the_same_inputs():
    from bench import datagen

    traffic = {"clients": 6, "samples_per_client": 4, "digits_per_client": 2,
               "test_per_class": 3}
    a, b = datagen.make(traffic, 2**31 + 77), datagen.make(traffic, 2**31 + 77)
    c = datagen.make(traffic, 2**31 + 78)
    for k in a:
        assert (a[k] == b[k]).all()
    assert not (a["client_x"] == c["client_x"]).all()
    # One held-out set for every seed: the evaluation compiles once.
    assert (a["test_x"] == c["test_x"]).all()
    assert (a["test_y"] == c["test_y"]).all()
    assert a["client_x"].shape == (6, 4, 28, 28)
    assert all(len(set(row)) == 2 for row in a["client_y"].tolist())


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         json.loads((REPO / "BENCHMARK.json").read_text())["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
