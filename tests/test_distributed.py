"""Distributed-runtime tests: run in subprocesses with fake host devices so
the main pytest process keeps the 1-device view (per the brief)."""

import subprocess
import sys
import textwrap

import pytest


def _run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env_code = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count={devices}'\n"
        "import sys; sys.path.insert(0, 'src')\n"
        "from repro.launch.mesh import make_mesh\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", env_code + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
def test_approx_allreduce_matches_mean_at_high_snr():
    """At very high SNR the approximate all-reduce equals the exact mean."""
    _run_py("""
        import jax, jax.numpy as jnp, functools
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import aggregation as AGG, transport as T, channel as CH

        mesh = make_mesh((4,), ("data",))
        cfg = T.TransportConfig(mode="approx", channel=CH.ChannelConfig(snr_db=60.0, fading="awgn"))
        g = jnp.linspace(-0.9, 0.9, 4 * 64).reshape(4, 64)

        @functools.partial(jax.shard_map, mesh=mesh, axis_names={"data"},
                           in_specs=P("data", None), out_specs=P())
        def agg(gl):
            out, stats = AGG.approx_allreduce(gl[0], jax.random.PRNGKey(0), cfg, ("data",))
            return out

        with jax.set_mesh(mesh):
            got = jax.jit(agg)(g)
        want = g.mean(0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
        print("OK")
    """)


@pytest.mark.slow
def test_train_step_approx_runs_and_descends():
    """Paper-faithful per-client uplink step on a 4x2 mesh: loss decreases
    over steps at moderate SNR."""
    out = _run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.core import transport as T, channel as CH
        from repro.launch import steps as S
        from repro.models import registry as R
        from repro.optim.sgd import sgd as make_sgd

        cfg = get_config("qwen2-1.5b").reduced(n_layers=2, d_model=64, d_ff=128, vocab_size=128)
        mesh = make_mesh((4, 2), ("data", "model"))
        tcfg = T.TransportConfig(mode="approx", channel=CH.ChannelConfig(snr_db=20.0))
        opt = make_sgd(0.2)
        key = jax.random.PRNGKey(0)
        params = R.init_params(key, cfg)
        opt_state = opt.init(params)
        tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab_size, jnp.int32)
        batch = {"tokens": tokens, "labels": tokens}
        with jax.set_mesh(mesh):
            step = jax.jit(S.make_train_step_approx(cfg, opt, tcfg, mesh))
            losses = []
            for i in range(6):
                key, sk = jax.random.split(key)
                params, opt_state, loss, stats = step(params, opt_state, batch, sk)
                losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        assert all(l == l for l in losses)  # no NaN
        print("LOSSES", losses)
    """)
    assert "LOSSES" in out


@pytest.mark.slow
def test_per_shard_corruption_step():
    """Fully-manual elementwise uplink corruption (kimi-k2 path)."""
    _run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.core import transport as T, channel as CH
        from repro.launch import steps as S
        from repro.models import registry as R
        from repro.optim.sgd import sgd as make_sgd

        cfg = get_config("qwen2-1.5b").reduced(n_layers=2, d_model=64, d_ff=128, vocab_size=128)
        mesh = make_mesh((4, 2), ("data", "model"))
        tcfg = T.TransportConfig(mode="approx", channel=CH.ChannelConfig(snr_db=25.0))
        opt = make_sgd(0.2)
        key = jax.random.PRNGKey(0)
        params = R.init_params(key, cfg)
        tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab_size, jnp.int32)
        batch = {"tokens": tokens, "labels": tokens}
        with jax.set_mesh(mesh):
            step = jax.jit(S.make_train_step(cfg, opt, transport_cfg=tcfg, mesh=mesh))
            p2, o2, loss = step(params, opt.init(params), batch, key)
        assert jnp.isfinite(loss), loss
        print("OK", float(loss))
    """)


@pytest.mark.slow
def test_dryrun_single_combo_small_mesh():
    """The dry-run driver itself (reduced arch, production-mesh code path)."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-1.5b",
         "--shape", "decode_32k", "--mesh", "single", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, timeout=560,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK qwen2-1.5b" in out.stdout


@pytest.mark.slow
def test_expert_parallel_moe_matches_dense():
    """shard_map + tiled all_to_all expert parallelism == dense dispatch."""
    _run_py("""
        import jax, jax.numpy as jnp, dataclasses
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.configs import get_config
        from repro.models import moe as MOE

        cfg = get_config("kimi-k2-1t-a32b").reduced(
            d_model=64, moe_d_ff=32, n_experts=8, top_k=2)
        cfg = dataclasses.replace(cfg, capacity_factor=4.0, n_shared_experts=1)
        mesh = make_mesh((4, 2), ("data", "model"))
        p = MOE.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)
        with jax.set_mesh(mesh):
            xd = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            pd = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, NamedSharding(mesh, P())), p)
            pe = dict(pd)
            for k2 in ("wi", "wg", "wo"):
                pe[k2] = jax.device_put(p[k2], NamedSharding(mesh, P("data", None, None)))
            d_out, d_aux = jax.jit(lambda x, p: MOE.moe_ffn(x, p, cfg))(xd, pd)
            e_out, e_aux = jax.jit(lambda x, p: MOE.moe_ffn_shardmap(x, p, cfg))(xd, pe)
        np.testing.assert_allclose(np.asarray(d_out), np.asarray(e_out),
                                   rtol=2e-4, atol=2e-4)
        # gradients flow through the all_to_all pair
        g = jax.jit(jax.grad(lambda p: jnp.sum(
            MOE.moe_ffn_shardmap(xd, p, cfg)[0].astype(jnp.float32) ** 2)))(pe)
        assert all(bool(jnp.isfinite(l).all()) for l in jax.tree_util.tree_leaves(g))
        print("OK")
    """)


@pytest.mark.slow
def test_bf16_wire_train_step():
    """Per-client uplink with the bf16 wire format descends and halves
    the reported airtime symbols."""
    out = _run_py("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config
        from repro.core import transport as T, channel as CH
        from repro.launch import steps as S
        from repro.models import registry as R
        from repro.optim.sgd import sgd as make_sgd

        cfg = get_config("qwen2-1.5b").reduced(n_layers=2, d_model=64, d_ff=128, vocab_size=128)
        mesh = make_mesh((4, 2), ("data", "model"))
        opt = make_sgd(0.2)
        key = jax.random.PRNGKey(0)
        params = R.init_params(key, cfg)
        tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab_size, jnp.int32)
        batch = {"tokens": tokens, "labels": tokens}
        syms = {}
        with jax.set_mesh(mesh):
            for wd in ("float32", "bfloat16"):
                tcfg = T.TransportConfig(mode="approx", wire_dtype=wd,
                                         channel=CH.ChannelConfig(snr_db=25.0))
                step = jax.jit(S.make_train_step_approx(cfg, opt, tcfg, mesh))
                p, o, loss, stats = step(params, opt.init(params), batch, key)
                assert jnp.isfinite(loss)
                syms[wd] = float(stats.data_symbols)
        assert abs(syms["bfloat16"] - syms["float32"] / 2) < 1e-3 * syms["float32"]
        print("SYMS", syms)
    """)
    assert "SYMS" in out
