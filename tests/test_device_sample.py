"""Device-resident client shards and the on-device minibatch gather.

The round engine puts the client shards on the device once, as
``(M·n, 784)`` image rows and ``(M, n)`` labels. Each round ``sample``
draws the rows on the host with the same ``default_rng`` call as a numpy
gather, sends the int32 indices (``sample_h2d_bytes``) and gathers on the
device, so a run takes the same rows in the same order as a numpy gather.

Runs are tiny (4 clients x 24 samples, one round).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.mnist_cnn import config as cnn_config
from repro.core import channel as CH
from repro.core import transport as T
from repro.data import synth_mnist
from repro.fl import engine as E
from repro.fl import partition
from repro.fl.async_engine import AsyncRoundEngine
from repro.obs import ledger as L

M, N = 4, 24

# Algorithm, and the shape of its row draw per round.
ALGOS = {
    "fedsgd": (lambda cfg: E.FedSGD(cfg, batch_per_round=8), (M, 8)),
    "fedavg": (lambda cfg: E.FedAvg(cfg, local_steps=2, batch_per_step=4),
               (M, 2, 4)),
}
ENGINES = {"sync": E.RoundEngine, "async": AsyncRoundEngine}


@pytest.fixture(scope="module")
def world():
    (img, lab), (ti, tl) = synth_mnist.train_test(60, 16, seed=0)
    parts = partition.non_iid_partition(img, lab, n_clients=M)
    cx, cy = partition.stack_clients(parts, per_client=N)
    return cx, cy, ti, tl


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(cnn_config(), lr=0.1)


def _tc():
    return T.TransportConfig(mode="approx",
                             channel=CH.ChannelConfig(snr_db=10.0))


def _numpy_gather(cx, cy, shape, seed):
    """The host gather the engine used before: the same draw, then
    ``np.take_along_axis`` on the numpy shards."""
    take = np.random.default_rng(seed).integers(0, cx.shape[1], shape)
    flat = take.reshape(shape[0], -1)
    xb = np.take_along_axis(cx, flat[:, :, None, None], axis=1)
    yb = np.take_along_axis(cy, flat, axis=1)
    return xb.reshape(shape + cx.shape[2:]), yb.reshape(shape)


def _shards(kind, cx, cy):
    if kind == "numpy":
        return cx, cy
    if kind == "device_rows":
        return E.device_shards(cx, cy)
    return jnp.asarray(cx), jnp.asarray(cy)


@pytest.mark.parametrize("kind", ["numpy", "device_rows", "device_4d"])
@pytest.mark.parametrize("algo_name", sorted(ALGOS))
def test_sample_equals_the_numpy_gather(cfg, world, algo_name, kind):
    """Numpy shards, the engine's device rows and 4-D device shards all
    give the numpy gather's images and labels, bit for bit."""
    cx, cy, _, _ = world
    make, shape = ALGOS[algo_name]
    x_in, y_in = _shards(kind, cx, cy)
    xb, yb = make(cfg).sample(np.random.default_rng(11), x_in, y_in)
    x_ref, y_ref = _numpy_gather(cx, cy, shape, 11)
    assert isinstance(xb, jax.Array) and isinstance(yb, jax.Array)
    assert xb.shape == shape + (28, 28) and xb.dtype == jnp.float32
    assert yb.shape == shape and yb.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(xb), x_ref)
    np.testing.assert_array_equal(np.asarray(yb), y_ref)


def test_device_shards_are_rows(world):
    cx, cy, _, _ = world
    rows, labels = E.device_shards(cx, cy)
    assert rows.shape == (M * N, 28 * 28) and labels.shape == (M, N)
    assert rows.dtype == jnp.float32 and labels.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(rows), cx.reshape(M * N, -1))
    np.testing.assert_array_equal(np.asarray(labels), cy)


@pytest.mark.parametrize("engine_name", sorted(ENGINES))
def test_engine_keeps_the_shards_on_the_device(cfg, world, engine_name):
    """After init the shards are device arrays; ``client_x`` still reads as
    the ``(M, n, 28, 28)`` images the engine was given."""
    cx, cy, ti, tl = world
    eng = ENGINES[engine_name](E.FedSGD(cfg, batch_per_round=8), _tc(), cx,
                               cy, ti, tl, n_rounds=1, seed=3)
    for arr in (eng.client_rows, eng.client_x, eng.client_y):
        assert isinstance(arr, jax.Array)
    assert eng.client_rows.shape == (M * N, 28 * 28)
    np.testing.assert_array_equal(np.asarray(eng.client_x), cx)
    np.testing.assert_array_equal(np.asarray(eng.client_y), cy)


@pytest.mark.parametrize("engine_name", sorted(ENGINES))
def test_rounds_gather_from_the_device_rows(cfg, world, engine_name,
                                            monkeypatch):
    """Every round hands ``sample`` the engine's device rows, never host
    shards."""
    cx, cy, ti, tl = world
    algo = E.FedSGD(cfg, batch_per_round=8)
    seen = []
    sample = algo.sample

    def recording(rng, client_x, client_y, tm):
        seen.append((client_x, client_y))
        return sample(rng, client_x, client_y, tm)

    monkeypatch.setattr(algo, "sample", recording)
    eng = ENGINES[engine_name](algo, _tc(), cx, cy, ti, tl, n_rounds=2,
                               seed=3)
    eng.run()
    assert len(seen) == 2
    for client_x, client_y in seen:
        assert client_x is eng.client_rows and client_y is eng.client_y


@pytest.mark.parametrize("engine_name,algo_name", [
    ("sync", "fedsgd"), ("sync", "fedavg"), ("async", "fedsgd")])
def test_sample_h2d_bytes_in_the_manifest(cfg, world, tmp_path, engine_name,
                                          algo_name):
    """The bytes ``sample`` sends a round are the int32 row indices alone:
    ``M·B·4`` for FedSGD, ``M·L·B·4`` for FedAvg."""
    cx, cy, ti, tl = world
    make, shape = ALGOS[algo_name]
    path = str(tmp_path / "ledger.jsonl")
    eng = ENGINES[engine_name](make(cfg), _tc(), cx, cy, ti, tl, n_rounds=1,
                               seed=3, ledger=path)
    want = 4 * int(np.prod(shape))
    assert eng.sample_h2d_bytes == want
    eng.run()
    assert L.read_ledger(path).manifest["sample_h2d_bytes"] == want


@pytest.mark.parametrize("engine_name", sorted(ENGINES))
def test_shards_and_batches_stay_uncommitted(cfg, world, engine_name):
    """The shards, the batches and so the parameters stay uncommitted, like
    batches copied from the host: a committed batch would commit the round's
    outputs and compile the round program again from round 1 on."""
    cx, cy, ti, tl = world
    eng = ENGINES[engine_name](E.FedSGD(cfg, batch_per_round=8), _tc(), cx,
                               cy, ti, tl, n_rounds=2, seed=3)
    assert not eng.client_rows.committed and not eng.client_y.committed
    xb, yb = eng.algo.sample(np.random.default_rng(0), eng.client_rows,
                             eng.client_y)
    assert not xb.committed and not yb.committed
    eng.run()
    assert not any(leaf.committed
                   for leaf in jax.tree_util.tree_leaves(eng.params))
