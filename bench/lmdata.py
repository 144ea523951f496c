"""Token shards of an LM cell: uniform ids over the configuration's
vocabulary slice.

A traffic file gives ``clients``, ``sequences_per_client``, ``seq_len`` and
``eval_sequences``; ``make`` turns them and ``--seed`` into each client's
``(sequences, seq_len + 1)`` rows (inputs and next-token labels in one
row), labels the round engine does not read, and the held-out rows. Ids
are drawn uniform over the slice, as a chip that holds an eighth of the
vocabulary sees the ids routed to it. The held-out set is one fixed set,
the same for every seed: the program compiles its evaluation with the set
inside (``bench.datagen`` keeps its digits' held-out set fixed likewise).
"""

from __future__ import annotations

import numpy as np

TEST_SEED = 0x7E57


def make(traffic: dict, seed: int, vocab: int) -> dict:
    m, n = traffic["clients"], traffic["sequences_per_client"]
    width = traffic["seq_len"] + 1
    rng = np.random.default_rng([seed, 0x70CE])
    test_rng = np.random.default_rng([TEST_SEED, 0x70CE])
    k = traffic["eval_sequences"]
    return {
        "client_x": rng.integers(0, vocab, (m, n, width), dtype=np.int32),
        "client_y": np.zeros((m, n), np.int32),
        "test_x": test_rng.integers(0, vocab, (k, width), dtype=np.int32),
        "test_y": np.zeros((k,), np.int32),
    }
