"""Ahead-of-time compiles of the Pallas uplink kernels for a TPU v5e.

The TPU compiler ships with jax and compiles for a described chip that is
not attached, so Mosaic's lowering rules (block tiling, SMEM/VMEM limits,
supported casts and reductions) are checked here, at the paper's cohort
(100 clients) and CNN width (21,840 parameters padded to 22,528 words),
without running anything, and so is the client-sharded uplink over the
four chips of the described host. Nothing runs, so results are not checked:
that is ``chip_smoke.py``'s job on the chip.

The topology is described inside a fixture, never while a module imports:
only one process may load the TPU library, and every test worker imports
every test file.
"""

import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import channel, float_codec, transport
from repro.kernels import ops
from repro.launch.sharding import shard_transmit_batch

kernels = importlib.import_module("repro.kernels.approx_channel")

C, N = 100, 22_528


@pytest.fixture(scope="module")
def topo():
    # Keep the TPU library's logs out of /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.array(topo.devices), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))


@pytest.mark.parametrize("word_bits", [32, 16], ids=["f32", "bf16"])
@pytest.mark.parametrize("bits_per_symbol", [2, 8], ids=["qpsk", "256qam"])
@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
@pytest.mark.parametrize("kernel", ["batch", "fused_aggregate"])
def test_uplink_kernel_compiles_for_v5e(one_chip, kernel, masked,
                                        bits_per_symbol, word_bits):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wire = jnp.bfloat16 if word_bits == 16 else jnp.float32
    args = [spec((C, N), wire), spec((C,), jnp.uint32),
            spec((C,), jnp.float32), spec((C,), jnp.float32)]
    fn = kernels.approx_channel_batch_pallas
    if kernel == "fused_aggregate":
        args.append(spec((C,), jnp.float32))
        fn = kernels.approx_channel_batch_aggregate_pallas
    params = dict(
        bits_per_symbol=bits_per_symbol, fading="rayleigh", word_bits=word_bits,
        clamp_mask=(float_codec.exponent_clamp_mask16(2.0) if word_bits == 16
                    else float_codec.exponent_clamp_mask(2.0)),
        valid_words=21_840, interpret=False)
    if masked:
        args.append(spec((), jnp.int32))
        call = jax.jit(lambda *a: fn(*a[:-1], num_active=a[-1], **params))
    else:
        call = jax.jit(lambda *a: fn(*a, **params))

    compiled = call.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("per_client_snr", [False, True],
                         ids=["homogeneous_snr", "per_client_snr"])
def test_sharded_uplink_compiles_for_four_v5e(data_mesh, monkeypatch,
                                               per_client_snr):
    """The client-sharded uplink with compiled kernel rows, under
    ``shard_map``'s varying-axes check, over a 4-chip ``data`` mesh."""
    # The described chips are not the backend: steer the kernel off the
    # interpreter that the CPU backend would pick.
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    clients = NamedSharding(data_mesh, PartitionSpec("data"))
    cfg = transport.TransportConfig(
        mode="approx", modulation="qpsk", use_kernel=True,
        channel=channel.ChannelConfig(snr_db=10.0))
    key = jax.random.PRNGKey(0)
    args = [jax.ShapeDtypeStruct((C, 21_840), jnp.float32, sharding=clients)]
    if per_client_snr:
        args.append(jax.ShapeDtypeStruct((C,), jnp.float32, sharding=clients))
    call = jax.jit(lambda x, s=None: shard_transmit_batch(
        x, key, cfg, data_mesh, snr_db=s))

    compiled = call.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_aggregate_kernel_past_two_to_the_32_symbols_compiles_for_v5e(
        one_chip):
    """One client of a 568.5M-float payload (9.1 G QPSK symbols, three seed
    segments) into a running aggregate aliased to the output: one wave of
    a streamed cohort."""
    d = 568_486_912

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    call = jax.jit(lambda x, s, n, g, w, acc: (
        kernels.approx_channel_batch_aggregate_pallas(
            x, s, n, g, w, acc=acc, fading="rayleigh",
            clamp_mask=float_codec.exponent_clamp_mask(2.0),
            interpret=False)), donate_argnums=5)
    compiled = call.lower(
        spec((1, d), jnp.float32), spec((1,), jnp.uint32),
        spec((1,), jnp.float32), spec((1,), jnp.float32),
        spec((1,), jnp.float32), spec((d,), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # The running aggregate is the output's buffer.
    assert compiled.memory_analysis().alias_size_in_bytes >= d * 4
