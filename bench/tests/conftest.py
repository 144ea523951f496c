"""Fixtures for the benchmark's CPU tests: a tiny copy of the benchmark.

``tiny_root`` is a directory laid out as a checkout: ``BENCHMARK.json`` with
one extra cell, ``tiny``, on a four-client traffic file, and a copy of
``bench/``. ``rehearse`` runs ``bench/run.py``'s ``main`` there in this
process, with the look for a TPU steered to the CPU.
"""

from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TINY_TRAFFIC = {"clients": 4, "batch_per_round": 4, "samples_per_client": 8,
                "digits_per_client": 2, "eval_every": 2, "test_per_class": 5,
                "check_rounds": 3}


@pytest.fixture
def tiny_root(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    manifest["workloads"].append({
        "name": "tiny", "config": manifest["configs"][0]["name"],
        "traffic": "tiny", "chips": 1, "why": "CPU rehearsal"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    (tmp_path / "bench" / "traffic" / "tiny.json").write_text(
        json.dumps(TINY_TRAFFIC))
    return tmp_path


@pytest.fixture
def rehearse(tiny_root, monkeypatch):
    """``rehearse(trace=0, seed=...) -> (exit code, stdout lines)``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from bench import run

    def cpu_device(chips, root):
        return ({"platform": "cpu", "kind": "cpu", "count": chips},
                {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                jax.devices()[:chips])

    monkeypatch.setattr(run, "device_info", cpu_device)

    def go(trace=0, seed=3_000_000_019, seconds=0.5):
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = run.main(["--workload", "tiny", "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=tiny_root)
        return rc, buf.getvalue().splitlines()

    yield go
    # The run sets process-wide JAX options; the next test starts clean.
    jax.config.update("jax_default_matmul_precision", None)
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
