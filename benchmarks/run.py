"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines. Default is the quick
single-core profile; ``--full`` runs paper-scale (100 clients, eta=0.01).

  PYTHONPATH=src python -m benchmarks.run [--only fig3,ber] [--full]
"""

from __future__ import annotations

import argparse
import sys
import time

SUITES = {
    "ber": ("benchmarks.ber_vs_snr", "BER vs SNR (paper Sec. V)"),
    "table1": ("benchmarks.msb_protection", "Gray 16-QAM MSB protection (Table I)"),
    "ecrt": ("benchmarks.ecrt_overhead", "LDPC E[tx] + airtime model"),
    "kernel": ("benchmarks.kernel_throughput", "fused kernel vs jnp reference"),
    "scaling": ("benchmarks.clients_scaling", "batched multi-client uplink scaling"),
    "fig3": ("benchmarks.accuracy_vs_time", "accuracy vs comm-time (Fig. 3)"),
    "fig4": ("benchmarks.same_snr_same_ber", "same-SNR / same-BER (Fig. 4)"),
    "fedavg": ("benchmarks.fedavg_ablation", "FedAvg + adaptive scaling ablation"),
    "roofline": ("benchmarks.roofline_report", "dry-run roofline summary"),
    "link": ("benchmarks.link_adaptation",
             "adaptive mode policy vs fixed transports across scenarios"),
    "fl_round": ("benchmarks.fl_round",
                 "uplink-vs-downlink error budget (Qu et al. asymmetry)"),
    "compression": ("benchmarks.compression",
                    "sparse top-k+EF uplink accuracy-vs-airtime Pareto"),
    "async_fl": ("benchmarks.async_fl",
                 "buffered-async vs sync FL under straggling (FedBuff)"),
    "obs": ("benchmarks.obs_smoke",
            "run ledger + Perfetto trace + phase timers smoke"),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    picks = [s.strip() for s in args.only.split(",") if s.strip()] or list(SUITES)
    unknown = [p for p in picks if p not in SUITES]
    if unknown:
        print(f"unknown suite(s): {', '.join(unknown)}", file=sys.stderr)
        print(f"valid suites: {', '.join(SUITES)}", file=sys.stderr)
        raise SystemExit(2)

    from benchmarks import common
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # One emit-record sidecar per invocation (benchmarks/common.emit
    # appends; without the reset, records would accumulate across runs).
    common.reset_records()
    print("name,us_per_call,derived")
    failed = []
    for name in picks:
        mod_name, desc = SUITES[name]
        print(f"# === {name}: {desc} ===")
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run(quick=not args.full)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0.0,{e!r}", file=sys.stdout)
            failed.append(name)
        print(f"# {name} done in {time.time()-t0:.1f}s")
    if failed:
        # Remaining suites still ran (the ERROR lines above are per-suite),
        # but the invocation as a whole must fail: suites double as gates —
        # e.g. the link suite asserts bucketed ≡ select bit-equivalence.
        print(f"# FAILED suites: {', '.join(failed)}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
