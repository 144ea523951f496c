"""Training driver: FedSGD with the approximate wireless uplink.

Runs a *real* training loop (concrete arrays) on whatever devices exist —
on this CPU container use a reduced config + host-device mesh, e.g.:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --mesh-shape 4,2 --steps 20 --batch 8 --seq 256 --mode approx

The full production meshes are exercised by ``launch.dryrun`` (compile-only
on this container). This driver is the end-to-end example harness.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import INPUT_SHAPES, get_config
from repro.core import channel as channel_lib
from repro.core import transport as transport_lib
from repro.data.tokens import TokenStream
from repro.launch import sharding as sh
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_mesh
from repro.models import registry as R
from repro.optim.sgd import sgd as make_sgd


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh-shape", default="")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--mode", default="approx",
                    choices=["perfect", "naive", "approx", "ecrt"])
    ap.add_argument("--snr-db", type=float, default=10.0)
    ap.add_argument("--modulation", default="qpsk")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--checkpoint", default="")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(n_layers=4, d_model=256, d_ff=512, vocab_size=1024)

    tcfg = transport_lib.TransportConfig(
        mode=args.mode,
        modulation=args.modulation,
        channel=channel_lib.ChannelConfig(snr_db=args.snr_db),
        simulate_fec=False,
        ecrt_expected_tx=1.1,
        use_kernel=args.use_kernel,
    )
    opt = make_sgd(args.lr)

    n_dev = len(jax.devices())
    if args.mesh_shape:
        shape = tuple(int(x) for x in args.mesh_shape.split(","))
    else:
        shape = (n_dev, 1)
    mesh = make_mesh(shape, ("data", "model"))
    print(f"mesh {dict(mesh.shape)} devices={n_dev}")

    key = jax.random.PRNGKey(0)
    params = R.init_params(key, cfg)
    opt_state = opt.init(params)
    n_params = sum(p.size for p in jax.tree_util.tree_leaves(params))
    print(f"{args.arch} ({'reduced' if args.reduced else 'full'}): "
          f"{n_params/1e6:.1f}M params, mode={args.mode}")

    stream = TokenStream(cfg.vocab_size, args.seq, args.batch)
    with jax.set_mesh(mesh):
        if args.mode in ("approx", "naive"):
            step = jax.jit(steps_lib.make_train_step_approx(cfg, opt, tcfg, mesh))
        else:
            t = None if args.mode == "perfect" else tcfg
            step = jax.jit(steps_lib.make_train_step(
                cfg, opt, transport_cfg=t, mesh=mesh))
        for i in range(args.steps):
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in stream.next_batch().items()}
            key, sk = jax.random.split(key)
            out = step(params, opt_state, batch, sk)
            params, opt_state, loss = out[0], out[1], out[2]
            loss = float(loss)
            if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {loss:.4f} ({time.time()-t0:.2f}s)")
    if args.checkpoint:
        from repro import checkpoint as ckpt

        ckpt.save(args.checkpoint, params, step=args.steps)
        print("saved", args.checkpoint)
    return loss


if __name__ == "__main__":
    main()
