"""Train a ~100M-param transformer with the approximate-uplink all-reduce.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/train_llm_approx.py --steps 200

Each of the 4 data shards plays a client cohort: its gradients pass through
an independently-faded QPSK channel (bit-30 clamp, no FEC) before the psum.
This is the production-mesh pattern from launch/steps.py at host scale.
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import channel as CH
from repro.core import transport as T
from repro.data.tokens import TokenStream
from repro.launch import steps as S
from repro.launch.mesh import make_mesh
from repro.models import registry as R
from repro.optim.sgd import sgd as make_sgd


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--snr-db", type=float, default=15.0)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    # ~100M-param qwen2-family config
    cfg = dataclasses.replace(
        get_config("qwen2-1.5b"),
        n_layers=8, d_model=512, n_heads=8, n_kv_heads=2, head_dim=64,
        d_ff=2048, vocab_size=32000)
    n_dev = len(jax.devices())
    dshape = (n_dev // 2, 2) if n_dev >= 4 else (n_dev, 1)
    mesh = make_mesh(dshape, ("data", "model"))

    tcfg = T.TransportConfig(mode="approx",
                             channel=CH.ChannelConfig(snr_db=args.snr_db))
    opt = make_sgd(3e-2)
    key = jax.random.PRNGKey(0)
    params = R.init_params(key, cfg)
    n = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"model {n/1e6:.0f}M params, mesh {dict(mesh.shape)}, "
          f"uplink approx@{args.snr_db}dB")

    stream = TokenStream(cfg.vocab_size, args.seq, args.batch)
    opt_state = opt.init(params)
    with jax.set_mesh(mesh):
        step = jax.jit(S.make_train_step_approx(cfg, opt, tcfg, mesh))
        for i in range(args.steps):
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
            key, sk = jax.random.split(key)
            params, opt_state, loss, stats = step(params, opt_state, batch, sk)
            if i % max(1, args.steps // 20) == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(loss):.4f} "
                      f"uplink_ber {float(stats.ber):.4f} ({time.time()-t0:.2f}s)")


if __name__ == "__main__":
    main()
