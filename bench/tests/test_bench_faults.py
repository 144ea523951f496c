"""``correct`` comes out false for the control and for each fault the cell
can have, with the rest of a run driven as usual (CPU, four clients).

The control is the reference put in the program's place with its products
at three bfloat16 passes, the step below the configuration's ``highest``
(``bf16_3x``: what a TPU computes at ``high``, spelled out, since the CPU
ignores the precision option). At the four clients a test can hold it reads
under the limits, so the reference in bfloat16 is the control that has to
fail here. The program's own bfloat16 wire is read too. The faults are
planted in the program underneath the timed path: a step that leaves the
state unchanged, half of the cohort left out with the mean taken over the
rest, and the aggregate altered where the uplink produces it."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run


def _patch_system(monkeypatch, patch):
    resolve = run.resolve_cell

    def patched(root, workload):
        cell = resolve(root, workload)
        patch(cell)
        return cell

    monkeypatch.setattr(run, "resolve_cell", patched)


def _incorrect(rehearse):
    rc, lines = rehearse(trace=0)
    assert rc == 0
    result = json.loads(lines[-1])
    assert result["correct"] is False
    return result


def _control(monkeypatch, **kw):
    """The reference, computed as ``kw`` says, in the program's place."""
    def patch(cell):
        system, cfg, traffic = cell["system"], cell["config"], cell["traffic"]

        def control(engine, n):
            data = {"client_x": np.asarray(engine.client_x),
                    "client_y": np.asarray(engine.client_y)}
            return system.reference(cfg, traffic, data, engine.seed, n, **kw)

        system.check_steps = control

    _patch_system(monkeypatch, patch)


def test_control_the_reference_at_three_bf16_passes(rehearse, monkeypatch):
    # On the CPU the program's client gradients equal the reference's bit
    # for bit. At four clients three passes move the numbers off that but
    # stay under the limits, which are set at the cells' sizes, where the
    # chip reads this control failing (bench/calibrate.py).
    _control(monkeypatch, precision="bf16_3x")
    rc, lines = rehearse(trace=0)
    assert rc == 0
    checks = json.loads(lines[-1])["checks"]
    assert checks["grad1_gap"]["value"] > 0
    assert checks["grad1_diff"]["value"] > 0


def test_control_the_reference_in_bfloat16(rehearse, monkeypatch):
    _control(monkeypatch, compute_dtype=jnp.bfloat16)
    _incorrect(rehearse)


def test_the_programs_bf16_wire(rehearse, monkeypatch):
    def patch(cell):
        build = cell["system"].build
        cell["system"].build = lambda *a, **k: build(*a, **k,
                                                     wire_dtype="bfloat16")

    _patch_system(monkeypatch, patch)
    _incorrect(rehearse)


def test_fault_step_leaves_the_state_unchanged(rehearse, monkeypatch):
    from repro.fl import engine

    monkeypatch.setattr(engine.FedSGD, "apply",
                        lambda self, params, opt_state, agg: (params, opt_state))
    result = _incorrect(rehearse)
    assert result["checks"]["grad1_gap"]["value"] == pytest.approx(1.0)


def test_fault_half_the_cohort_left_out(rehearse, monkeypatch):
    from repro.core import transport

    orig = transport.transmit_pytree_batch_aggregate

    def half(tree, key, cfg, weights, **kw):
        m = weights.shape[0]
        keep = (jnp.arange(m) < m // 2).astype(jnp.float32)
        return orig(tree, key, cfg, keep / jnp.sum(keep), **kw)

    monkeypatch.setattr(transport, "transmit_pytree_batch_aggregate", half)
    _incorrect(rehearse)


def test_fault_aggregate_altered_where_it_is_produced(rehearse, monkeypatch):
    from repro.core import transport

    orig = transport.transmit_pytree_batch_aggregate

    def altered(tree, key, cfg, weights, **kw):
        agg, stats = orig(tree, key, cfg, weights, **kw)
        return dict(agg, conv2_w=-agg["conv2_w"]), stats

    monkeypatch.setattr(transport, "transmit_pytree_batch_aggregate", altered)
    _incorrect(rehearse)
