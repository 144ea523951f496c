"""The system under test for FedSGD with a decoder LM payload, and its plain
reference.

``build`` constructs the program's ``RoundEngine`` as ``repro.fl.loop.run_fl``
does (a ``FedSGD`` algorithm over an ``LmPayload``, the configuration's
transport, no scenario, the fused aggregate), with the harness's span sink
as ``phase_timers``; the engine picks its own cohort waves. The model is
built from the configuration file's keys alone. The token shards come
from ``bench.lmdata``; the digits ``bench/run.py`` makes for every cell
are not used.

``check_steps`` drives the engine through its first rounds and keeps the
parameters after round 1, the program's gradient function (the payload
function the round uses, one client a call as the round's waves batch
it) and each round's MoE counters. ``reference`` draws round 1's inputs
from the seed alone (``bench.refmath_moonlight.round_inputs``: the
parameters at round 0, the routers' biases, every client's minibatch and
the round key). ``compare`` then decides ``correct`` at the timed sizes,
client by client:

* ``grad_rel_l2``: the program's gradients of every client's minibatch
  against the reference's float32 gradients at ``highest`` precision, by
  the relative L2 norm of the difference over the cohort. The program's
  single bf16 pass a product moves the routers' inputs enough to send
  some tokens to another expert, which moves the whole gradient by more
  than the rounding itself; a zero gradient reads 1;
* ``round1_step_rel``: the parameters' change in round 1 against the
  reference's own round (its gradients through its uplink, widened
  counter, summed in client order, times the learning rate), by the
  relative L2 norm of the difference: a round that leaves the state
  unchanged reads 1. The uplink turns the few per cent by which the two
  gradients differ into whole received words wherever a float's exponent
  differs, so a sound round reads well above rounding;
* ``round1_off_share`` and ``round1_scale_gap``: the parameters after
  round 1 against ``params0 - lr * agg``, where ``agg`` is the reference
  uplink applied to the program's own gradients, so that only the round's
  channel, aggregation and step are judged. ``round1_off_share`` is the
  share of elements further than ``OFF_ULPS`` float32 ulps at the scale of
  the step's terms (``|params0| + lr * sum_c w_c |x_hat_c|``), which
  rounding in another order never is, and further than ``OFF_STEP`` of
  the step's terms (``lr * sum_c w_c |x_hat_c|``): a symbol that the
  round's channel decides otherwise than the reference's, or a word sent
  elsewhere. ``round1_scale_gap`` is ``|1 - beta|`` for the least-squares
  ``beta`` of the program's step on the reference's, which reads the
  learning rate and a share of the cohort left out.

A sound round reads a small ``round1_off_share`` (about 1e-4 on a v5e):
symbols on a decision boundary, which the kernel's noise and the
reference's may decide apart, and elements where the gradient recomputed
outside the round rounds otherwise.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import lmdata
from bench import refmath_moonlight as refm


def model_config(cfg: dict):
    """The program's ``ModelConfig`` from the configuration file's keys."""
    from repro.configs.base import ModelConfig

    ep, m = cfg["expert_parallel"], cfg["model"]
    return ModelConfig(
        name=cfg["name"], family="moe", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        vocab_size=cfg["vocab_size"], q_lora_rank=cfg["q_lora_rank"] or 0,
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], n_experts=ep["router_experts"],
        experts_held=cfg["n_routed_experts"],
        expert_offset=ep["expert_offset"],
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        n_shared_experts=cfg["n_shared_experts"],
        dense_d_ff=cfg["intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        router_score=cfg["scoring_func"], norm_topk_prob=cfg["norm_topk_prob"],
        routed_scale=cfg["routed_scaling_factor"],
        router_bias_std=m["router_bias_std"],
        capacity_factor=m["capacity_factor"], attn_impl=m["attn_impl"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype="float32")


def build(cfg: dict, traffic: dict, data: dict, seed: int, sink):
    """The program's round engine for this configuration and traffic."""
    from repro.core import channel as channel_lib
    from repro.core import transport as transport_lib
    from repro.fl import engine as engine_lib
    from repro.fl.payload import LmPayload

    t = cfg["transport"]
    tcfg = transport_lib.TransportConfig(
        mode=t["mode"], modulation=t["modulation"],
        channel=channel_lib.ChannelConfig(
            snr_db=t["snr_db"], fading=t["fading"],
            block_len=t.get("block_len", 64), tx_power=t["tx_power"],
            distance=t["distance"], pathloss_exp=t["pathloss_exp"]),
        clamp_bound=t["clamp_bound"], wire_dtype=t["wire_dtype"],
        use_kernel=t["use_kernel"])
    tokens = lmdata.make(traffic, seed, cfg["vocab_size"])
    model = LmPayload(model_config(cfg), cfg["model"]["lr"],
                      traffic["seq_len"])
    algo = engine_lib.FedSGD(model, batch_per_round=traffic["batch_per_round"])
    return engine_lib.RoundEngine(
        algo, tcfg, tokens["client_x"], tokens["client_y"], tokens["test_x"],
        tokens["test_y"], n_rounds=1, seed=seed,
        eval_every=traffic["eval_every"], scenario=cfg["scenario"],
        fused_aggregate=cfg["fused_aggregate"], phase_timers=sink)


@contextlib.contextmanager
def _recording_first_round(engine, record):
    """Wrap the engine's round step so the first call's output parameters
    are copied to the host; the original step is put back on exit."""
    step = engine._round_step

    def recorded(*args):
        out = step(*args)
        if not record:
            record["p1"] = jax.device_get(out[0])
        return out

    engine._round_step = recorded
    try:
        yield
    finally:
        engine._round_step = step


@jax.jit
def _flat(tree):
    return jnp.concatenate([a[0].reshape(-1).astype(jnp.float32)
                            for a in jax.tree_util.tree_leaves(tree)])


def check_steps(engine, n: int) -> dict:
    """Drive ``engine`` through its first ``n`` rounds in one ``run`` call
    and keep what :func:`compare` needs."""
    p0 = jax.device_get(engine.params)
    record = {}
    engine.n_rounds = n
    with _recording_first_round(engine, record):
        res = engine.run()
    payload = jax.jit(engine.algo.payload)

    def grad(params, x):
        """The program's gradient of one client's ``(1, B, S + 1)`` rows,
        flat ``(D,)``."""
        return _flat(payload(params, x, np.zeros(x.shape[:2], np.int32)))

    return dict(record, p0=p0, grad=grad,
                counters=[r.counters for r in res.records])


def reference(cfg: dict, traffic: dict, data: dict, seed: int, n: int, *,
              dtype=jnp.float32, widen: bool = True) -> dict:
    """Round 1's inputs from the seed, and the plain reference's functions
    for this configuration: the model's gradient (``dtype`` its
    arithmetic) and one client's uplink (``widen`` its counter)."""
    s = refm.shapes(cfg)
    shards = lmdata.make(traffic, seed, cfg["vocab_size"])["client_x"]
    return dict(
        refm.round_inputs(cfg, traffic, seed, shards),
        shapes=s, grad=refm.grad_fn(s, dtype),
        uplink=reference_uplink(cfg, widen), clients=traffic["clients"])


def reference_uplink(cfg: dict, widen: bool = True):
    """The reference's jitted uplink of one client, ``(x, key) -> x_hat``."""
    transport = cfg["transport"]
    return jax.jit(lambda x, k: refm.uplink_client(
        x, k, transport=transport, widen=widen))


# A parameter after round 1 is off when it lies more than this many ulps
# from the reference step (rounding in another order reads a unit or two)
# and more than this share of the step's terms from it: at a tenth, sound
# rounds on a v5e read about 1e-4 of the elements off and a learning rate
# half again too large 0.4; at a half, 3e-5 and 0.06.
OFF_ULPS = 16
OFF_STEP = 0.1


@functools.partial(jax.jit, static_argnums=5)
def _leaf_numbers(p1, p0, agg, mag, agg_ref, off_steps, lr):
    """One leaf's sums: elements off the step ``lr * agg`` for each share
    of ``off_steps`` (see ``OFF_ULPS``), the products of the least-squares
    scale, and the squared change against the reference's own step."""
    step, scale = lr * agg, lr * mag
    s = jnp.maximum(jnp.abs(p0) + scale, jnp.float32(2.0 ** -126))
    ulp = jnp.ldexp(jnp.float32(1.0), jnp.frexp(s)[1] - 24)
    gap = jnp.abs(p1 - (p0 - step))
    off = [jnp.sum(gap > jnp.maximum(OFF_ULPS * ulp, f * scale))
           for f in off_steps]
    change = p0 - p1
    ref_step = lr * agg_ref
    return (off, jnp.sum(change * step), jnp.sum(step * step),
            jnp.sum(jnp.square(change - ref_step)), jnp.sum(ref_step ** 2))


@functools.partial(jax.jit, donate_argnums=0)
def _accumulate(agg, hat, w):
    """``agg + w * hat``, in place."""
    return agg + w * hat


def _ss(a: np.ndarray, block: int = 1 << 24) -> float:
    """Sum of squares of a host vector, in float64 a block at a time."""
    return float(sum(np.dot(b, b) for b in (
        a[i:i + block].astype(np.float64) for i in range(0, a.size, block))))


def round_terms(grad, ref: dict) -> dict:
    """Client by client, the program's gradient function ``grad`` and the
    reference's on the reference's inputs: their squared difference and
    the reference's squared norm, summed; the reference uplink's weighted
    sum of the program's gradients (``agg``, on the device, in client
    order) and of their magnitudes (``mag``); and the reference's own
    aggregate (``agg_ref``). All but ``agg`` are kept on the host, so
    that the chip holds one client's gradient at a time besides the
    parameters and ``agg``."""
    p0, biases, xb = ref["p0"], ref["biases"], ref["xb"]
    w = np.float32(1.0 / ref["clients"])
    agg = agg_ref = mag = None
    diff = norm = 0.0
    for c in range(ref["clients"]):
        k = jax.random.fold_in(ref["round_key"], c)
        g = ref["grad"](p0, biases, xb[c])
        hat = np.asarray(ref["uplink"](g, k))
        agg_ref = w * hat if agg_ref is None else agg_ref + w * hat
        g_ref = np.array(g)
        del g, hat
        norm += _ss(g_ref)
        g = grad(p0, xb[c:c + 1])
        g_ref -= np.asarray(g)
        diff += _ss(g_ref)
        del g_ref
        hat = ref["uplink"](g, k)
        del g
        agg = _accumulate(jnp.zeros_like(hat) if agg is None else agg, hat,
                          jnp.float32(w))
        hat = np.abs(np.asarray(hat))
        mag = w * hat if mag is None else mag + w * hat
        del hat
    return {"diff": diff, "norm": norm, "agg": agg, "mag": mag,
            "agg_ref": agg_ref}


def step_numbers(p1, p0_prog, terms: dict, ref: dict, lr: float,
                 off_steps: tuple = ()) -> dict:
    """The round's numbers for the parameters ``p1`` after round 1 (a
    tree) against the terms of :func:`round_terms`; ``init_mismatch`` is
    the share of the program's round-0 parameters ``p0_prog`` that differ
    from the reference's."""
    agg, mag, agg_ref = terms["agg"], terms["mag"], terms["agg_ref"]
    steps = (OFF_STEP,) + tuple(off_steps)
    off = np.zeros(len(steps), np.int64)
    dot = sq = dev = ref_sq = 0.0
    start = mismatch = 0
    lr = jnp.float32(lr)
    for a0, a0p, a1 in zip(jax.tree_util.tree_leaves(ref["p0"]),
                           jax.tree_util.tree_leaves(p0_prog),
                           jax.tree_util.tree_leaves(p1)):
        part = slice(start, start + a0.size)
        a0 = a0.reshape(-1)
        mismatch += int(np.sum(np.asarray(a0) != np.asarray(a0p).reshape(-1)))
        n_off, d, q, e, r = _leaf_numbers(
            jnp.asarray(a1).reshape(-1), a0, agg[part],
            jnp.asarray(mag[part]), jnp.asarray(agg_ref[part]), steps, lr)
        off += np.asarray([int(x) for x in n_off])
        dot, sq = dot + float(d), sq + float(q)
        dev, ref_sq = dev + float(e), ref_sq + float(r)
        start += a0.size
    out = {
        "round1_step_rel": (dev / ref_sq) ** 0.5,
        "round1_off_share": float(off[0]) / start,
        "round1_scale_gap": abs(1.0 - dot / sq),
        "init_mismatch": mismatch / start,
    }
    for f, n_off in zip(steps[1:], off[1:]):
        out[f"round1_off_share@{f:g}"] = float(n_off) / start
    return out


def compare(prog: dict, ref: dict, lr: float,
            off_steps: tuple = ()) -> dict:
    """The numbers that decide ``correct`` (see the module docstring), and
    readings: ``moe_local_tokens``, ``init_mismatch`` and
    ``round1_off_share`` at each further share of ``off_steps``."""
    terms = round_terms(prog["grad"], ref)
    counters = [c or {} for c in prog["counters"]]
    return dict(
        {"grad_rel_l2": (terms["diff"] / terms["norm"]) ** 0.5},
        **step_numbers(prog["p1"], prog["p0"], terms, ref, lr, off_steps),
        moe_local_tokens=float(sum(c.get("moe_local_tokens", 0)
                                   for c in counters)))


# ------------------------------------------------- controls and faults


def bf16_round(prog: dict, ref: dict, lr: float) -> dict:
    """What the reference gives when computed in bfloat16, the precision
    below the configuration's float32, in the form ``check_steps`` keeps
    the program's round: its gradients in bfloat16, sent on the reference
    uplink, summed and applied to bfloat16 parameters."""
    bf = jnp.bfloat16
    grad = refm.grad_fn(ref["shapes"], bf)
    w = jnp.asarray(1.0 / ref["clients"], bf)
    agg = None
    for c in range(ref["clients"]):
        g = grad(ref["p0"], ref["biases"], ref["xb"][c]).astype(bf)
        hat = ref["uplink"](g.astype(jnp.float32),
                            jax.random.fold_in(ref["round_key"], c))
        del g
        term = w * hat.astype(bf)
        agg = term if agg is None else agg + term
        del hat, term
    p1, start = [], 0
    leaves, treedef = jax.tree_util.tree_flatten(ref["p0"])
    for a in leaves:
        part = agg[start:start + a.size].reshape(a.shape)
        p1.append(np.asarray((a.astype(bf) - jnp.asarray(lr, bf) * part)
                             .astype(jnp.float32)))
        start += a.size
    del agg

    def grad_f32(params, x):
        return grad(params, ref["biases"], x[0])

    return dict(prog, p1=jax.tree_util.tree_unflatten(treedef, p1),
                grad=grad_f32)

def round_work(cfg: dict, traffic: dict) -> dict:
    """Operations and bytes one round needs, from shapes alone."""
    return refm.work(cfg, traffic)

