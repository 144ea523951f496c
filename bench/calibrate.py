"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds <n> --first-seed <s> \
        [--controls <k>] [--out <file.jsonl>]

For each seed the program's first rounds are compared with the plain
reference at the cell's own size, as a run of ``bench/run.py`` does: the
lower readings. On the first ``--controls`` seeds the same numbers are read
for the controls and the planted faults, each put in the program's place:

* ``program_high``: the program with JAX's matmul precision at ``high``
  (three bfloat16 passes), the step below the configuration's ``highest``;
* ``program_default``: the program at JAX's default, one bfloat16 pass;
* ``control_high``: the reference with its products at ``high``;
* ``control_bf16``: the reference with its whole CNN in bfloat16;
* ``wire_bf16``: the program with its own lower-precision wire on;
* ``fault_half``: half of the cohort left out, the mean taken over the rest;
* ``fault_altered``: the change of one leaf altered where it is produced,
  its sign flipped.

A step that returns its state unchanged reads 1 on every gap by
construction and needs no run. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402


def altered(ref: dict, leaf: str) -> dict:
    """``ref`` with the change of ``leaf`` negated at every step."""
    out = {"p0": ref["p0"], "bit_errors": ref["bit_errors"], "params": []}
    for p in ref["params"]:
        q = dict(p)
        q[leaf] = 2 * ref["p0"][leaf] - p[leaf]
        out["params"].append(q)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    bench_run.add_paths(ROOT)
    cell = bench_run.resolve_cell(ROOT, args.workload)
    cfg, traffic, system = cell["config"], cell["traffic"], cell["system"]
    bench_run.init_jax(ROOT, cfg)
    try:
        bench_run.device_info(cell["cell"]["chips"], ROOT)
    except bench_run.NoAccelerator as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import datagen

    n, m = traffic["check_rounds"], traffic["clients"]

    def compare(prog, ref):
        return system.compare(prog, ref, cfg["model"]["lr"])

    def program(data, seed, **kw):
        return system.check_steps(system.build(cfg, traffic, data, seed, None,
                                               **kw), n)

    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + i
        rec = {"workload": args.workload, "seed": seed}
        t = time.perf_counter()
        data = datagen.make(traffic, seed)
        prog = program(data, seed)
        rec["program_s"] = time.perf_counter() - t
        t = time.perf_counter()
        ref = system.reference(cfg, traffic, data, seed, n)
        rec["reference_s"] = time.perf_counter() - t
        rec["program"] = compare(prog, ref)
        if i < args.controls:
            for prec in ("high", "default"):
                with jax.default_matmul_precision(prec):
                    rec[f"program_{prec}"] = compare(
                        program(data, seed), ref)
            rec["control_high"] = compare(system.reference(
                cfg, traffic, data, seed, n, precision="high"), ref)
            rec["control_bf16"] = compare(system.reference(
                cfg, traffic, data, seed, n, compute_dtype=jnp.bfloat16), ref)
            rec["wire_bf16"] = compare(
                program(data, seed, wire_dtype="bfloat16"), ref)
            half = np.arange(m) < m // 2
            rec["fault_half"] = compare(system.reference(
                cfg, traffic, data, seed, n, client_mask=half), ref)
            rec["fault_altered"] = compare(
                altered(ref, "conv2_w"), ref)
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
