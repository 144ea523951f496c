"""``bench/run.py`` on the LM system, end to end on the CPU at a tiny
Moonlight-shaped size: a checkout laid out like the benchmark with one
extra cell, ``tinylm``, whose configuration keeps the cell's keys and cuts
every size; the look for a TPU is steered to the CPU in the test."""

import io
import json
import shutil
from contextlib import contextmanager, redirect_stdout
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
CELL = "moonlight-ep8-static-qpsk.silo4"
TINY_MODEL = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "num_hidden_layers": 5, "vocab_size": 64,
}
TINY_TRAFFIC = {"clients": 4, "batch_per_round": 2, "seq_len": 16,
                "sequences_per_client": 6, "eval_every": 2,
                "eval_sequences": 3, "check_rounds": 2,
                "samples_per_client": 2, "digits_per_client": 2,
                "test_per_class": 1}


def _lay_out(root):
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = json.loads((REPO / config["file"]).read_text())
    cfg.update(TINY_MODEL)
    cfg["expert_parallel"] = dict(cfg["expert_parallel"], router_experts=8)
    (root / "bench" / "configs" / "tinylm.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tinylm.json").write_text(
        json.dumps(TINY_TRAFFIC))
    manifest["configs"].append(dict(config, name="tinylm",
                                    file="bench/configs/tinylm.json"))
    manifest["workloads"].append(dict(cell, name="tinylm", config="tinylm",
                                      traffic="tinylm"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tinylm")
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@contextmanager
def _rehearsal(root, monkeypatch):
    """``go(trace=0, seed=...) -> (exit code, result)`` at ``root``, with
    the look for a TPU steered to the CPU."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from bench import run

    def cpu_device(chips, root):
        return ({"platform": "cpu", "kind": "cpu", "count": chips},
                {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
                jax.devices()[:chips])

    monkeypatch.setattr(run, "device_info", cpu_device)

    def go(trace=0, seed=3_000_000_151):
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = run.main(["--workload", "tinylm", "--seed", str(seed),
                           "--seconds", "0.5", "--trace", str(trace)],
                          root=root)
        return rc, json.loads(buf.getvalue().splitlines()[-1])

    try:
        yield go
    finally:
        jax.config.update("jax_default_matmul_precision", None)
        jax.config.update("jax_compilation_cache_dir", None)
        compilation_cache.reset_cache()


@pytest.fixture
def rehearse_lm(tmp_path, monkeypatch):
    with _rehearsal(_lay_out(tmp_path), monkeypatch) as go:
        yield go


@pytest.fixture(scope="module")
def exact_run(tmp_path_factory):
    """One untouched run, shared by the tests that read it."""
    with pytest.MonkeyPatch.context() as mp:
        with _rehearsal(_lay_out(tmp_path_factory.mktemp("lm")), mp) as go:
            return go(trace=0)


def test_the_lm_cell_runs_and_is_correct(exact_run):
    rc, result = exact_run
    assert rc == 0
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}
    checks = result["checks"]
    assert set(checks) == {"grad_rel_l2", "round1_step_rel",
                           "round1_off_share", "round1_scale_gap"}
    # On the CPU the program's float32 products are exact enough that the
    # gradient meets the reference to rounding, and the round is the
    # reference channel over its own gradients to an ulp or two, but for
    # the odd word whose symbols the rounding sends elsewhere.
    assert checks["grad_rel_l2"]["value"] < 1e-4
    assert checks["round1_step_rel"]["value"] < 1e-2
    assert checks["round1_off_share"]["value"] < 1e-4
    assert checks["round1_scale_gap"]["value"] < 1e-4


def test_the_reference_draws_the_programs_round_inputs_from_the_seed():
    import jax
    import numpy as np

    from bench import lmdata
    from bench import refmath_moonlight as refm
    from bench.systems import fedsgd_lm_round as system

    cfg = json.loads((REPO / "bench" / "configs"
                      / "moonlight-ep8-static-qpsk.json").read_text())
    cfg.update(TINY_MODEL)
    cfg["expert_parallel"] = dict(cfg["expert_parallel"], router_experts=8)
    seed = 3_000_000_177
    tokens = lmdata.make(TINY_TRAFFIC, seed, cfg["vocab_size"])
    engine = system.build(cfg, TINY_TRAFFIC, None, seed, None)
    xb, _ = engine.algo.sample(np.random.default_rng(seed),
                               engine.client_rows, engine.client_y)
    ref = refm.round_inputs(cfg, TINY_TRAFFIC, seed, tokens["client_x"])
    prog_leaves = jax.tree_util.tree_leaves_with_path(engine.params)
    ref_leaves = jax.tree_util.tree_leaves_with_path(ref["p0"])
    assert [p for p, _ in prog_leaves] == [p for p, _ in ref_leaves]
    for (_, a), (_, b) in zip(prog_leaves, ref_leaves):
        np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                      np.asarray(b).view(np.uint32))
    np.testing.assert_array_equal(np.asarray(engine.algo.model.buffers),
                                  np.asarray(ref["biases"]))
    np.testing.assert_array_equal(np.asarray(xb), ref["xb"])
    key = jax.random.split(jax.random.PRNGKey(seed))[0]
    np.testing.assert_array_equal(np.asarray(engine._key),
                                  np.asarray(key))
    np.testing.assert_array_equal(
        np.asarray(jax.random.split(engine._key)[1]),
        np.asarray(ref["round_key"]))


def test_a_traced_lm_run_leaves_the_kernel_metrics_out_off_the_chip(
        rehearse_lm):
    rc, result = rehearse_lm(trace=1)
    assert rc == 0 and result["correct"] is True
    # No device plane in a CPU trace: the lm_* readers find nothing.
    assert set(result["metrics"]) == {"sample_ms", "round_mfu",
                                      "compiles_per_round", "device_idle_share"}


def _patch_system(monkeypatch, patch):
    from bench import run

    resolve = run.resolve_cell

    def patched(root, workload):
        cell = resolve(root, workload)
        patch(cell["system"])
        return cell

    monkeypatch.setattr(run, "resolve_cell", patched)


def test_the_bf16_reference_reads_further_from_the_program(
        rehearse_lm, monkeypatch, exact_run):
    # The reference's round in bfloat16, the precision below the
    # configuration's float32, in the program's place: the harness's own
    # comparison refuses it, by the round's step and not by the gradient.
    _, exact = exact_run

    def patch(system):
        compare = system.compare
        system.compare = lambda prog, ref, lr, **k: compare(
            system.bf16_round(prog, ref, lr), ref, lr, **k)

    _patch_system(monkeypatch, patch)
    _, control = rehearse_lm(trace=0)
    checks = control["checks"]
    assert (checks["grad_rel_l2"]["value"]
            > 100 * exact["checks"]["grad_rel_l2"]["value"])
    assert control["correct"] is False
    assert checks["round1_off_share"]["value"] > 0.3
    assert checks["grad_rel_l2"]["value"] <= checks["grad_rel_l2"]["limit"]


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(
        rehearse_lm, monkeypatch):
    from repro.fl import engine

    monkeypatch.setattr(engine.FedSGD, "apply",
                        lambda self, params, opt_state, agg: (params, opt_state))
    _, result = rehearse_lm(trace=0)
    assert result["correct"] is False
    assert result["checks"]["round1_off_share"]["value"] > 0.1
    assert abs(result["checks"]["round1_step_rel"]["value"] - 1) < 1e-6
    assert abs(result["checks"]["round1_scale_gap"]["value"] - 1) < 1e-6


def test_a_learning_rate_half_again_too_large_is_not_correct(
        rehearse_lm, monkeypatch):
    from repro.fl import payload

    init = payload.LmPayload.__init__

    def patched(self, cfg, lr, seq_len):
        init(self, cfg, 1.5 * lr, seq_len)

    monkeypatch.setattr(payload.LmPayload, "__init__", patched)
    _, result = rehearse_lm(trace=0)
    assert result["correct"] is False
    assert abs(result["checks"]["round1_scale_gap"]["value"] - 0.5) < 1e-3
    assert result["checks"]["grad_rel_l2"]["value"] < 1e-4


def test_a_round_that_leaves_half_the_batch_out_is_not_correct(
        rehearse_lm, monkeypatch):
    from repro.fl import payload

    loss = payload.LmPayload.loss

    def half(self, params, x, y):
        # A client's minibatch loses half its rows; the held-out loss,
        # one row a call, keeps its row.
        return loss(self, params, x[:max(1, x.shape[0] // 2)], y)

    monkeypatch.setattr(payload.LmPayload, "loss", half)
    _, result = rehearse_lm(trace=0)
    assert result["correct"] is False
    assert result["checks"]["grad_rel_l2"]["value"] > 0.3
    assert result["checks"]["round1_step_rel"]["value"] > 0.3


def test_the_round_work_counts_the_published_shapes():
    from bench import refmath_moonlight as refm

    cfg = json.loads((REPO / "bench" / "configs"
                      / "moonlight-ep8-static-qpsk.json").read_text())
    traffic = json.loads((REPO / "bench" / "traffic" / "silo4.json")
                         .read_text())
    assert refm.n_params(cfg) == 568_484_352
    work = refm.work(cfg, traffic)
    assert work["payload"] == 568_484_352
    assert work["uplink_bytes"] == 4 * 568_484_352 * 4 + 568_484_352 * 4 + 16
    # Forward of 8192 tokens a client: about 4.9 TFLOP; the round three times
    # that for four clients.
    fwd = refm.forward_flops(cfg, 4, 2048)
    assert 4.5e12 < fwd < 5.5e12
    assert work["train_flops"] == 4 * 3 * fwd
