"""Plain reference math of one FedSGD round, and the work it needs.

Nothing here imports the program under test. The CNN is the paper's model
(arXiv:2304.03359 Sec. V) in straightforward float32 ``jax.numpy`` at
``highest`` matmul precision; the uplink is a copy of the counter-RNG channel
math of the repository's plain oracle (Gray-QAM, Rayleigh channel, closed-form
demap, exponent clamp, bit-error counters). A lower ``precision`` gives the
lower-precision control of the correctness check: ``high`` on the TPU, or
``bf16_3x``, the same three bfloat16 products spelled out, on any backend.

``cnn_flops_per_image`` and ``uplink_bytes`` count the operations and bytes
the work needs, from shapes alone; the per-layer metrics divide by them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128
BLOCK_WORDS = 1024  # one PHY frame per tile of 8 x 128 words
HIGHEST = jax.lax.Precision.HIGHEST

_U32 = jnp.uint32
_TWO_PI = 6.283185307179586
_STREAM_NOISE = 0x9E3779B9
_STREAM_FADE = 0x7FEB352D
_STREAM_PHASE = 0x68E31DA4
BITS_PER_SYMBOL = {"qpsk": 2, "16qam": 4, "256qam": 8}


# ------------------------------------------------------------------ the CNN


def cnn_init(key, model: dict) -> dict:
    """He-initialized parameters: conv(k) x2 + fc x2, zero biases."""
    k = jax.random.split(key, 4)
    c1, c2 = model["conv_channels"]
    kk = model["kernel"]
    side = ((model["image_size"] - kk + 1) // 2 - kk + 1) // 2
    flat = c2 * side * side

    def he(key_, shape, fan):
        return jax.random.normal(key_, shape, jnp.float32) * jnp.sqrt(2.0 / fan)

    return {
        "conv1_w": he(k[0], (c1, 1, kk, kk), kk * kk),
        "conv1_b": jnp.zeros((c1,), jnp.float32),
        "conv2_w": he(k[1], (c2, c1, kk, kk), c1 * kk * kk),
        "conv2_b": jnp.zeros((c2,), jnp.float32),
        "fc1_w": he(k[2], (flat, model["fc_hidden"]), flat),
        "fc1_b": jnp.zeros((model["fc_hidden"],), jnp.float32),
        "fc2_w": he(k[3], (model["fc_hidden"], model["n_classes"]),
                    model["fc_hidden"]),
        "fc2_b": jnp.zeros((model["n_classes"],), jnp.float32),
    }


def _three_pass(f, a, b):
    """``f`` bilinear: each operand split into bfloat16 high and low parts,
    and the three products that matter summed."""
    def split(v):
        hi = v.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (v - hi).astype(jnp.bfloat16).astype(jnp.float32)

    (ah, al), (bh, bl) = split(a), split(b)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def _product(f, a, b, precision: str):
    """``f(a, b, lax_precision)`` at ``precision``: one of JAX's names, or
    ``bf16_3x``, what a TPU computes at ``high`` spelled out for any
    backend: three bfloat16 passes in the forward products and in both
    products of their backward."""
    if precision != "bf16_3x":
        return f(a, b, jax.lax.Precision[precision.upper()])

    def exact(x, y):
        return f(x, y, HIGHEST)

    @jax.custom_vjp
    def prod(x, y):
        return _three_pass(exact, x, y)

    def fwd(x, y):
        return prod(x, y), (x, y)

    def bwd(res, ct):
        x, y = res
        dx = _three_pass(lambda c, y_: jax.vjp(
            lambda x_: exact(x_, y_), x)[1](c)[0], ct, y)
        dy = _three_pass(lambda x_, c: jax.vjp(
            lambda y_: exact(x_, y_), y)[1](c)[0], x, ct)
        return dx, dy

    prod.defvjp(fwd, bwd)
    return prod(a, b)


def _conv_op(x, w, precision):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=precision)


def _dot_op(x, w, precision):
    return jnp.dot(x, w, precision=precision)


def _conv(x, w, b, precision):
    return _product(_conv_op, x, w, precision) + b[None, :, None, None]


def _pool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 1, 2, 2), (1, 1, 2, 2), "VALID")


def cnn_logits(params, images, precision="highest"):
    """``(B, 28, 28)`` images -> ``(B, classes)`` logits."""
    x = images[:, None]
    x = _pool2(jax.nn.relu(_conv(x, params["conv1_w"], params["conv1_b"],
                                 precision)))
    x = _pool2(jax.nn.relu(_conv(x, params["conv2_w"], params["conv2_b"],
                                 precision)))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(_product(_dot_op, x, params["fc1_w"], precision)
                    + params["fc1_b"])
    return _product(_dot_op, x, params["fc2_w"], precision) + params["fc2_b"]


def cnn_loss(params, images, labels, precision="highest"):
    """Mean cross-entropy."""
    logp = jax.nn.log_softmax(cnn_logits(params, images, precision), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def client_grads(params, xb, yb, compute_dtype=jnp.float32,
                 precision="highest"):
    """Per-client gradients, flattened in sorted-leaf order: ``(M, D)`` f32.

    The payload layout of the uplink: each client's leaves, sorted by name,
    concatenated. ``compute_dtype=bfloat16`` computes the whole CNN, its
    gradient included, in bfloat16; ``precision`` is that of every
    convolution and matrix product."""
    p = jax.tree_util.tree_map(lambda a: a.astype(compute_dtype), params)

    def one(x, y):
        g = jax.grad(cnn_loss)(p, x.astype(compute_dtype), y, precision)
        return jnp.concatenate([g[k].reshape(-1).astype(jnp.float32)
                                for k in sorted(g)])

    return jax.vmap(one)(xb, yb)


def cnn_flops_per_image(model: dict) -> dict:
    """Operations the forward and backward passes need for one image.

    A multiply-add counts 2. Backward computes every weight gradient and the
    input gradient of every layer but the first (the images need none).
    Bias adds, activations, pooling and the softmax are left out."""
    s, kk = model["image_size"], model["kernel"]
    c1, c2 = model["conv_channels"]
    h1 = s - kk + 1
    h2 = h1 // 2 - kk + 1
    flat = c2 * (h2 // 2) ** 2
    layers = [
        2 * h1 * h1 * c1 * kk * kk,            # conv1
        2 * h2 * h2 * c2 * c1 * kk * kk,       # conv2
        2 * flat * model["fc_hidden"],         # fc1
        2 * model["fc_hidden"] * model["n_classes"],  # fc2
    ]
    fwd = sum(layers)
    bwd = fwd + sum(layers[1:])
    return {"forward": fwd, "backward": bwd, "train": fwd + bwd}


def n_params(model: dict) -> int:
    """Length of the uplink payload: the CNN's parameter count."""
    shapes = jax.eval_shape(lambda: cnn_init(jax.random.PRNGKey(0), model))
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))


def uplink_bytes(clients: int, payload: int, wire_bytes: int = 4) -> int:
    """Bytes one fused uplink-and-aggregate launch needs to move.

    Each client's payload is read once at its wire width, the f32 aggregate
    is written once, and each client writes one 4-byte error counter.
    Padding and masked rows are not work, so they do not count."""
    return clients * payload * wire_bytes + payload * 4 + 4 * clients


# -------------------------------------------- the uplink (counter-RNG PHY)


def fmix32(x):
    """murmur3 finalizer."""
    x = x.astype(_U32)
    x = x ^ (x >> 16)
    x = x * _U32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * _U32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _hash(seed, idx, stream):
    return fmix32(seed.astype(_U32)
                  ^ fmix32(idx.astype(_U32) * _U32(0x9E3779B9) + _U32(stream)))


def _uniform01(h):
    h24 = jax.lax.bitcast_convert_type(h >> 8, jnp.int32)
    return (h24.astype(jnp.float32) * jnp.float32(1.0 / 16777216.0)
            + jnp.float32(2.0 ** -25))


def _gauss_pair(seed, idx, stream):
    u1 = _uniform01(_hash(seed, idx, stream))
    u2 = _uniform01(_hash(seed, idx, stream ^ _STREAM_PHASE))
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    ang = jnp.float32(_TWO_PI) * u2
    return r * jnp.cos(ang), r * jnp.sin(ang)


def _gray_encode(n):
    return n ^ (n >> 1)


def _gray_decode(g):
    for s in (1, 2, 4):
        g = g ^ (g >> s)
    return g


def _popcount(x):
    x = x - ((x >> 1) & _U32(0x55555555))
    x = (x & _U32(0x33333333)) + ((x >> 2) & _U32(0x33333333))
    x = (x + (x >> 4)) & _U32(0x0F0F0F0F)
    return (x * _U32(0x01010101)) >> 24


def _as_u32(x):
    return jax.lax.bitcast_convert_type(x, _U32)


def _as_i32(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def channel_tile(u, seed, base_sym, noise_power, gain, *, k, fading,
                 fade_block, word_bits=32):
    """Words of one ``(R, 128)`` tile -> received words (before the clamp).

    Symbol ``j`` (MSB-first) of word ``w`` goes out at tile position
    ``j * R * 128 + w``; noise and fading come from a hash of the symbol's
    global index and the client's seed."""
    p = k // 2
    levels = 1 << p
    bw = u.size
    s_per_word = word_bits // k
    amp = math.sqrt(3.0 / (2.0 * (levels * levels - 1)))
    shape = (s_per_word,) + u.shape
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    shifts = _as_u32(word_bits - k * (j + 1))
    stream = (jnp.broadcast_to(u[None], shape) >> shifts) & _U32((1 << k) - 1)

    gi = jnp.zeros_like(stream)
    gq = jnp.zeros_like(stream)
    for b in range(p):
        gi = gi | (((stream >> _U32(k - 1 - 2 * b)) & _U32(1)) << _U32(p - 1 - b))
        gq = gq | (((stream >> _U32(k - 2 - 2 * b)) & _U32(1)) << _U32(p - 1 - b))
    s_re = (2.0 * _as_i32(_gray_decode(gi)).astype(jnp.float32)
            - (levels - 1)) * jnp.float32(amp)
    s_im = (2.0 * _as_i32(_gray_decode(gq)).astype(jnp.float32)
            - (levels - 1)) * jnp.float32(amp)

    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    gidx = _as_u32(base_sym + j * bw + rows * shape[-1] + lanes)

    n_re, n_im = _gauss_pair(seed, gidx, _STREAM_NOISE)
    nscale = jnp.sqrt(noise_power * 0.5)
    n_re, n_im = n_re * nscale, n_im * nscale
    if fading == "awgn":
        c_re = jnp.sqrt(gain) * jnp.ones_like(s_re)
        c_im = jnp.zeros_like(s_re)
    else:
        fidx = gidx // _U32(fade_block) if fading == "block_rayleigh" else gidx
        h_re, h_im = _gauss_pair(seed, fidx, _STREAM_FADE)
        hs = jnp.sqrt(jnp.float32(0.5))
        c_re = jnp.sqrt(gain) * h_re * hs
        c_im = jnp.sqrt(gain) * h_im * hs
    c2 = jnp.maximum(c_re * c_re + c_im * c_im, jnp.float32(1e-20))
    y_re = s_re + (n_re * c_re + n_im * c_im) / c2
    y_im = s_im + (n_im * c_re - n_re * c_im) / c2

    inv = jnp.float32(1.0 / amp)

    def level(x):
        lvl = jnp.round((x * inv + (levels - 1)) * 0.5)
        return _as_u32(jnp.clip(lvl, 0, levels - 1).astype(jnp.int32))

    gi_hat = _gray_encode(level(y_re))
    gq_hat = _gray_encode(level(y_im))
    rx = jnp.zeros_like(stream)
    for b in range(p):
        rx = rx | (((gi_hat >> _U32(p - 1 - b)) & _U32(1)) << _U32(k - 1 - 2 * b))
        rx = rx | (((gq_hat >> _U32(p - 1 - b)) & _U32(1)) << _U32(k - 2 - 2 * b))
    u_hat = rx[0] << _U32(word_bits - k)
    for s in range(1, s_per_word):
        u_hat = u_hat | (rx[s] << _U32(word_bits - k * (s + 1)))
    return u_hat


def clamp_mask(bound: float) -> int:
    """AND-mask clearing the leading exponent bits that are 0 for |g| < bound."""
    e_max = max(0, min(254, 127 + math.ceil(math.log2(bound)) - 1))
    mask = 0xFFFFFFFF
    for b in range(8 - max(1, e_max.bit_length())):
        mask &= ~(1 << (30 - b))
    return mask


def seed_from_key(key):
    """A client's channel seed, drawn from its uplink key."""
    return jax.random.randint(key, (), 0, jnp.iinfo(jnp.int32).max,
                              dtype=jnp.int32).astype(_U32)


def uplink_client(x, key, *, transport: dict):
    """One client's ``(D,)`` f32 payload through the approximate uplink.

    Returns ``(x_hat (D,) f32, bit_errors int32)``."""
    if transport["mode"] != "approx" or transport["wire_dtype"] != "float32":
        raise ValueError("the reference uplink covers the approx mode on the "
                         "float32 wire")
    k = BITS_PER_SYMBOL[transport["modulation"]]
    gain = transport["tx_power"] * transport["distance"] ** (
        -transport["pathloss_exp"])
    noise_power = gain / (10.0 ** (float(transport["snr_db"]) / 10.0))
    d = x.shape[0]
    pad = (-d) % BLOCK_WORDS
    u = jax.lax.bitcast_convert_type(jnp.pad(x, (0, pad)), _U32)
    tiles = u.reshape(-1, BLOCK_WORDS // LANES, LANES)
    seed = seed_from_key(key)
    base = jnp.arange(tiles.shape[0], dtype=jnp.int32) * (BLOCK_WORDS * (32 // k))
    u_hat = jax.vmap(lambda t, b: channel_tile(
        t, seed, b, jnp.float32(noise_power), jnp.float32(gain), k=k,
        fading=transport["fading"], fade_block=transport.get("block_len", 64),
    ))(tiles, base).reshape(-1)
    u_hat = u_hat & _U32(clamp_mask(transport["clamp_bound"]))
    errs = jnp.sum(_as_i32(_popcount(u[:d] ^ u_hat[:d])))
    return jax.lax.bitcast_convert_type(u_hat[:d], jnp.float32), errs


def unflatten(flat, like: dict) -> dict:
    """``(D,)`` payload -> a parameter dict shaped as ``like``."""
    out, off = {}, 0
    for name in sorted(like):
        size = int(np.prod(like[name].shape))
        out[name] = flat[off:off + size].reshape(like[name].shape)
        off += size
    return out
