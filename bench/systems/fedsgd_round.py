"""The system under test for FedSGD configurations, and its plain reference.

``build`` constructs the program's ``RoundEngine`` exactly as
``repro.fl.loop.run_fl`` does (a ``FedSGD`` algorithm, the configuration's
transport, no scenario, the fused aggregate), with the harness's span sink
as ``phase_timers``. ``check_steps`` drives that same engine through its
first rounds and keeps the parameters after each; ``reference`` computes
the same rounds from the seed with ``bench.refmath`` alone; ``compare``
turns both into the numbers that decide ``correct``.
"""

from __future__ import annotations

import contextlib
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from bench import refmath

# Leaves whose reference gradient norm is below this share of the median
# leaf's are nought to rounding and left out of every gap.
NOUGHT_SHARE = 1e-3


def build(cfg: dict, traffic: dict, data: dict, seed: int, sink, *,
          wire_dtype: str | None = None):
    """The program's round engine for this configuration and traffic.

    ``wire_dtype`` puts the program's own lower-precision wire in place of
    the configuration's (a reading of the calibration)."""
    from repro.configs.mnist_cnn import MnistCnnConfig
    from repro.core import channel as channel_lib
    from repro.core import transport as transport_lib
    from repro.fl import engine as engine_lib

    m, t = cfg["model"], cfg["transport"]
    cnn_cfg = MnistCnnConfig(
        image_size=m["image_size"], conv_channels=tuple(m["conv_channels"]),
        kernel=m["kernel"], fc_hidden=m["fc_hidden"],
        n_classes=m["n_classes"], lr=m["lr"])
    tcfg = transport_lib.TransportConfig(
        mode=t["mode"], modulation=t["modulation"],
        channel=channel_lib.ChannelConfig(
            snr_db=t["snr_db"], fading=t["fading"],
            block_len=t.get("block_len", 64), tx_power=t["tx_power"],
            distance=t["distance"], pathloss_exp=t["pathloss_exp"]),
        clamp_bound=t["clamp_bound"],
        wire_dtype=wire_dtype or t["wire_dtype"],
        use_kernel=t["use_kernel"])
    algo = engine_lib.FedSGD(cnn_cfg, batch_per_round=traffic["batch_per_round"])
    return engine_lib.RoundEngine(
        algo, tcfg, data["client_x"], data["client_y"], data["test_x"],
        data["test_y"], n_rounds=1, seed=seed,
        eval_every=traffic["eval_every"], scenario=cfg["scenario"],
        fused_aggregate=cfg["fused_aggregate"], phase_timers=sink)


@contextlib.contextmanager
def _recording_round_step(engine, record):
    """Wrap the engine's round step (no link scenario) so each call's
    outputs are copied to the host; the original step is put back on
    exit."""
    step = engine._round_step

    def recorded(*args):
        out = step(*args)
        record.append(jax.device_get((out[0], out[2].bit_errors)))
        return out

    engine._round_step = recorded
    try:
        yield
    finally:
        engine._round_step = step


def check_steps(engine, n: int) -> dict:
    """Drive ``engine`` through its first ``n`` rounds in one ``run`` call
    (so every round samples new rows) and keep what each round produced."""
    p0 = jax.device_get(engine.params)
    record = []
    engine.n_rounds = n
    with _recording_round_step(engine, record):
        engine.run()
    return {"p0": _flat(p0), "params": [_flat(p) for p, _ in record],
            "bit_errors": [float(np.sum(e)) for _, e in record]}


def _flat(params) -> dict:
    return {k: np.asarray(v, np.float32).reshape(-1) for k, v in params.items()}


def _block(m: int, cap: int = 25) -> int:
    return max(d for d in range(1, cap + 1) if m % d == 0)


@functools.lru_cache(maxsize=8)
def _round_fn(transport_json: str, m: int, dtype_name: str, precision: str):
    """The jitted reference round for one cohort size and precision: client
    gradients, each client's uplink, and the weighted sum, block by block."""
    transport = json.loads(transport_json)
    blk = _block(m)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def round_agg(params, xb, yb, rk, weights):
        xb = xb.reshape((m // blk, blk) + xb.shape[1:])
        yb = yb.reshape((m // blk, blk) + yb.shape[1:])
        ids = jnp.arange(m, dtype=jnp.int32).reshape(m // blk, blk)
        wb = weights.reshape(m // blk, blk)

        def one_block(args):
            x, y, i, w = args
            g = refmath.client_grads(params, x, y, dtype, precision)
            keys = jax.vmap(lambda c: jax.random.fold_in(rk, c))(i)
            hat, errs = jax.vmap(lambda gc, kc: refmath.uplink_client(
                gc, kc, transport=transport))(g, keys)
            return jnp.sum(w[:, None] * hat, axis=0), errs

        parts, errs = jax.lax.map(one_block, (xb, yb, ids, wb))
        agg = parts[0]
        for b in range(1, parts.shape[0]):
            agg = agg + parts[b]
        return agg, jnp.sum(errs)

    return round_agg


def reference(cfg: dict, traffic: dict, data: dict, seed: int, n: int, *,
              compute_dtype=jnp.float32, precision: str = "highest",
              client_mask=None) -> dict:
    """The first ``n`` rounds from the seed, computed by ``bench.refmath``.

    The same sampling, key schedule and payload layout as the paper's round:
    params from ``split(PRNGKey(seed))``, one ``split`` per round, client
    ``i`` on ``fold_in(round_key, i)``, minibatch rows drawn by
    ``default_rng(seed)``, every client weighted alike. Clients go through
    in blocks, so the reference fits on the chip once the program's state
    is freed. ``client_mask`` (0/1 per client) leaves clients out of the
    mean; ``compute_dtype`` and ``precision`` set the CNN's arithmetic."""
    model = cfg["model"]
    cx, cy = data["client_x"], data["client_y"]
    m, n_local = cx.shape[0], cx.shape[1]
    batch = traffic["batch_per_round"]
    lr = jnp.float32(model["lr"])
    mask = jnp.ones((m,), jnp.float32) if client_mask is None else \
        jnp.asarray(client_mask, jnp.float32)
    weights = mask / jnp.sum(mask)
    round_agg = _round_fn(json.dumps(cfg["transport"], sort_keys=True), m,
                          jnp.dtype(compute_dtype).name, precision)

    key = jax.random.PRNGKey(seed)
    key, pk = jax.random.split(key)
    params = refmath.cnn_init(pk, model)
    names = sorted(params)
    rng = np.random.default_rng(seed)
    out = {"p0": _flat(jax.device_get(params)), "params": [], "bit_errors": []}
    for _ in range(n):
        key, rk = jax.random.split(key)
        take = rng.integers(0, n_local, (m, batch))
        xb = np.take_along_axis(cx, take[:, :, None, None], axis=1)
        yb = np.take_along_axis(cy, take, axis=1)
        agg, errs = round_agg(params, jnp.asarray(xb), jnp.asarray(yb), rk,
                              weights)
        agg = refmath.unflatten(agg, params)
        params = {k: params[k] - lr * agg[k] for k in names}
        out["params"].append(_flat(jax.device_get(params)))
        out["bit_errors"].append(float(errs))
    return out


def _leaf_gaps(prog: dict, ref: dict, keep) -> float:
    """Worst leaf's gap of norms, against the larger of that leaf's
    reference norm and the median leaf's."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(prog[k])) - norms[k])
               / max(norms[k], med, 1e-30) for k in keep)


def _leaf_diffs(prog: dict, ref: dict, keep) -> float:
    """Worst leaf's norm of the difference, on the same scale."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    return max(float(np.linalg.norm(prog[k] - ref[k]))
               / max(norms[k], med, 1e-30) for k in keep)


def _outliers(prog: dict, ref: dict, keep, rel: float) -> int:
    """Elements of the kept leaves whose gap to the reference is over
    ``rel`` times their leaf's root mean square in the reference."""
    count = 0
    for k in keep:
        rms = float(np.sqrt(np.mean(np.square(ref[k], dtype=np.float64))))
        count += int(np.sum(np.abs(prog[k] - ref[k]) > rel * max(rms, 1e-30)))
    return count


def _median_dev(prog: dict, ref: dict, keep) -> float:
    """Median, over every element of the kept leaves, of its gap to the
    reference in units of its leaf's root mean square in the reference."""
    devs = []
    for k in keep:
        rms = float(np.sqrt(np.mean(np.square(ref[k], dtype=np.float64))))
        devs.append(np.abs(prog[k] - ref[k]) / max(rms, 1e-30))
    return float(np.median(np.concatenate(devs)))


# An element of the first gradient is an outlier when its gap to the
# reference is over this share of its leaf's root mean square.
OUTLIER_LEVEL = 1e-3


def compare(prog: dict, ref: dict, lr: float) -> dict:
    """The numbers that decide ``correct``, and further readings; the
    configuration's ``limits`` name the ones compared.

    ``grad1_gap``: the first aggregate gradient as the optimizer received
    it, ``(p0 - p1) / lr``, by the worst leaf's gap of norms. ``delta3_gap``:
    the same for the change of the parameters over the checked rounds.
    ``grad1_diff`` and ``delta3_diff``: the norms of the differences on the
    gaps' scale, which see a leaf altered without its norm changing.
    ``grad1_median_dev``: the median element's gap in the first gradient,
    which the rounding of every product sets. ``grad1_outlier_share``: the
    share of the first gradient's elements off by more than
    ``OUTLIER_LEVEL`` of their leaf's RMS. The noisy uplink turns a small
    difference at an exponent boundary into a different received word, and
    a max-pool window whose two largest inputs lie within rounding of each
    other sends a client's gradient down another path: rare events that
    move the share and the worst leaf on some seeds but leave the median
    element alone. ``bit_errors1_gap``: the first round's uplink error
    count against the reference's."""
    g1 = {k: (prog["p0"][k] - prog["params"][0][k]) / lr for k in prog["p0"]}
    g1_ref = {k: (ref["p0"][k] - ref["params"][0][k]) / lr for k in ref["p0"]}
    d_last = {k: prog["params"][-1][k] - prog["p0"][k] for k in prog["p0"]}
    d_ref = {k: ref["params"][-1][k] - ref["p0"][k] for k in ref["p0"]}
    norms = {k: float(np.linalg.norm(v)) for k, v in g1_ref.items()}
    med = float(np.median(list(norms.values())))
    keep = [k for k in norms if norms[k] >= NOUGHT_SHARE * med]
    elements = sum(g1_ref[k].size for k in keep)
    e_ref = ref["bit_errors"][0]
    return {
        "grad1_gap": _leaf_gaps(g1, g1_ref, keep),
        "delta3_gap": _leaf_gaps(d_last, d_ref, keep),
        "grad1_outlier_share": _outliers(g1, g1_ref, keep, OUTLIER_LEVEL)
        / elements,
        "grad1_median_dev": _median_dev(g1, g1_ref, keep),
        "grad1_diff": _leaf_diffs(g1, g1_ref, keep),
        "delta3_diff": _leaf_diffs(d_last, d_ref, keep),
        "bit_errors1_gap": abs(prog["bit_errors"][0] - e_ref) / max(e_ref, 1.0),
    }


def round_work(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes one round needs, from shapes alone."""
    model = cfg["model"]
    flops = refmath.cnn_flops_per_image(model)
    clients = traffic["clients"]
    payload = refmath.n_params(model)
    wire = 2 if cfg["transport"]["wire_dtype"] == "bfloat16" else 4
    return {
        "train_flops": clients * traffic["batch_per_round"] * flops["train"],
        "eval_flops": 10 * traffic["test_per_class"] * flops["forward"],
        "uplink_bytes": refmath.uplink_bytes(clients, payload, wire),
        "payload": payload,
    }
