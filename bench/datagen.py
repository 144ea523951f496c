"""The one traffic generator: non-IID client shards of synthetic digits.

A traffic file (``bench/traffic/<name>.json``) gives the numbers; this
module turns them and ``--seed`` into the client data. The digits follow
the repository's procedural MNIST-like generator (seven-segment glyphs with
jitter, intensity and pixel noise, ``repro.data.synth_mnist``) and its
non-IID split (``repro.fl.partition``: each client holds
``digits_per_client`` digit classes, an equal share of each), vectorized so
that a thousand clients are made in a second or two. The copy keeps the
yardstick fixed whatever later changes the program's own generators.
"""

from __future__ import annotations

import numpy as np

_SEGS = {
    "A": (0, 2, 1, 11), "B": (1, 10, 10, 12), "C": (10, 19, 10, 12),
    "D": (18, 20, 1, 11), "E": (10, 19, 0, 2), "F": (1, 10, 0, 2),
    "G": (9, 11, 1, 11),
}
_DIGIT_SEGS = ("ABCDEF", "BC", "ABGED", "ABGCD", "FGBC", "AFGCD", "AFGEDC",
               "ABC", "ABCDEFG", "ABCDFG")
SIDE = 28


def _glyphs() -> np.ndarray:
    g = np.zeros((10, 20, 12), np.float32)
    for d, segs in enumerate(_DIGIT_SEGS):
        for s in segs:
            r0, r1, c0, c1 = _SEGS[s]
            g[d, r0:r1, c0:c1] = 1.0
    return g


def render(labels: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """``(N,)`` digit labels -> ``(N, 28, 28)`` f32 images in [0, 1]."""
    n = labels.shape[0]
    dy = rng.integers(0, 8, n)
    dx = rng.integers(0, 16, n)
    inten = rng.uniform(0.7, 1.0, n).astype(np.float32)
    img = rng.standard_normal((n, SIDE, SIDE), dtype=np.float32)
    img *= np.float32(0.12)
    rows = dy[:, None, None] + np.arange(20)[None, :, None]
    cols = dx[:, None, None] + np.arange(12)[None, None, :]
    idx = np.arange(n)[:, None, None]
    img[idx, rows, cols] += _glyphs()[labels] * inten[:, None, None]
    np.clip(img, 0.0, 1.0, out=img)
    return img


def client_labels(clients: int, per_client: int, digits: int,
                  rng: np.random.Generator) -> np.ndarray:
    """``(clients, per_client)`` labels: client ``c`` holds the digits at
    positions ``c*digits ..`` of a pool of shuffled permutations of 0-9,
    ``per_client / digits`` samples of each, in shuffled order."""
    if per_client % digits or 10 % digits:
        raise ValueError("samples_per_client must split evenly over the "
                         "client's digits, and the digits over 0-9")
    pool = np.concatenate([rng.permutation(10)
                           for _ in range(clients * digits // 10 + 1)])
    held = pool[:clients * digits].reshape(clients, digits)
    labels = np.repeat(held, per_client // digits, axis=1)
    return rng.permuted(labels, axis=1).astype(np.int32)


# The held-out evaluation set is one fixed set, as a dataset's test split
# is: the same for every seed. The program compiles its evaluation with the
# set inside, so a set drawn from the seed would compile anew in every run.
TEST_SEED = 0x7E57


def make(traffic: dict, seed: int) -> dict:
    """Client shards for one run, from ``seed``, and the fixed held-out
    evaluation set."""
    rng = np.random.default_rng([seed, 0x5EED])
    m, n = traffic["clients"], traffic["samples_per_client"]
    labels = client_labels(m, n, traffic["digits_per_client"], rng)
    images = render(labels.reshape(-1), rng).reshape(m, n, SIDE, SIDE)
    test_rng = np.random.default_rng([TEST_SEED, 0x5EED])
    test_y = np.repeat(np.arange(10, dtype=np.int32), traffic["test_per_class"])
    test_y = test_rng.permutation(test_y)
    test_x = render(test_y, test_rng)
    return {"client_x": images, "client_y": labels,
            "test_x": test_x, "test_y": test_y}
