"""Readings that the limits of an LM cell's ``correct`` are set from, on
the chip.

    python3 bench/calibrate_moonlight.py --workload <cell> --seeds <n> \
        --first-seed <s> [--controls <k>] [--out <file.jsonl>]

For each seed the program's first round is compared with the plain
reference at the cell's own size, as a run of ``bench/run.py`` compares
it (``fedsgd_lm_round.compare``), with ``round1_off_share`` read at
further shares of the step (``OFF_STEPS``). On the first ``--controls``
seeds the controls and faults are read besides:

* ``control_bf16``: the reference's round computed in bfloat16, the
  precision below the configuration's float32
  (``fedsgd_lm_round.bf16_round``), compared in the program's place;
* ``control_wrapped``: the comparison with the reference uplink's 32-bit
  symbol counter that wraps at 2^32 symbols (268M float32 words at QPSK);
* ``fault_unchanged``, ``fault_lr0.5``, ``fault_lr1.5``: the program's
  round with its step left out, halved or made half again as large;
* ``fault_half_batch``: a round whose clients each take the gradient of
  half their minibatch (rows 0 and 1 twice), sent on the reference
  uplink; the program's gradient function itself stays sound.

The faults' numbers are the round's (``step_numbers``) against the sound
program's terms. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402

OFF_STEPS = (0.5, 0.2, 0.05, 0.02)


class _NoSpans:
    @contextlib.contextmanager
    def scope(self, name):
        yield None


def _scaled_step(prog, s: float):
    """The program's parameters after round 1 with its step times ``s``."""
    import jax
    import numpy as np

    return jax.tree_util.tree_map(
        lambda a0, a1: a0 - np.float32(s) * (a0 - a1), prog["p0"], prog["p1"])


def _half_batch_round(prog, ref, lr: float):
    """Round 1 with each client's gradient of rows 0 and 1 of its
    minibatch, on the reference uplink, summed in client order."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b = ref["xb"].shape[1]
    rows = np.arange(b) % max(1, b // 2)
    agg = None
    w = jnp.float32(1.0 / ref["clients"])
    for c in range(ref["clients"]):
        g = prog["grad"](ref["p0"], ref["xb"][c:c + 1][:, rows])
        hat = ref["uplink"](g, jax.random.fold_in(ref["round_key"], c))
        del g
        agg = hat * w if agg is None else agg + w * hat
        del hat
    p1, start = [], 0
    leaves, treedef = jax.tree_util.tree_flatten(ref["p0"])
    for a in leaves:
        part = agg[start:start + a.size].reshape(a.shape)
        p1.append(np.asarray(a - jnp.float32(lr) * part))
        start += a.size
    return jax.tree_util.tree_unflatten(treedef, p1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--controls", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    bench_run.add_paths(ROOT)
    cell = bench_run.resolve_cell(ROOT, args.workload)
    cfg, traffic, system = cell["config"], cell["traffic"], cell["system"]
    bench_run.init_jax(ROOT, cfg)
    bench_run.device_info(cell["cell"]["chips"], ROOT)
    lr = cfg["model"]["lr"]
    sink = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + i
        t0 = time.perf_counter()
        engine = system.build(cfg, traffic, None, seed, _NoSpans())
        prog = system.check_steps(engine, 1)
        del engine
        gc.collect()
        ref = system.reference(cfg, traffic, None, seed, 1)
        row = {"seed": seed}
        if i < args.controls:
            terms = system.round_terms(prog["grad"], ref)
            row["program"] = dict(
                {"grad_rel_l2": (terms["diff"] / terms["norm"]) ** 0.5},
                **system.step_numbers(prog["p1"], prog["p0"], terms, ref, lr,
                                      OFF_STEPS))
            for name, s in (("fault_unchanged", 0.0), ("fault_lr0.5", 0.5),
                            ("fault_lr1.5", 1.5)):
                row[name] = system.step_numbers(
                    _scaled_step(prog, s), prog["p0"], terms, ref, lr,
                    OFF_STEPS)
            row["fault_half_batch"] = system.step_numbers(
                _half_batch_round(prog, ref, lr), prog["p0"], terms, ref, lr,
                OFF_STEPS)
            del terms
            gc.collect()
            row["control_bf16"] = system.compare(
                system.bf16_round(prog, ref, lr), ref, lr, OFF_STEPS)
            gc.collect()
            row["control_wrapped"] = system.compare(prog, dict(
                ref, uplink=system.reference_uplink(cfg, widen=False)), lr,
                OFF_STEPS)
        else:
            row["program"] = system.compare(prog, ref, lr, OFF_STEPS)
        row["seconds"] = time.perf_counter() - t0
        del prog, ref
        gc.collect()
        line = json.dumps(row)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
