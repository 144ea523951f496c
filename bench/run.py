"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/<c>.json``)
and a traffic mix (``bench/traffic/<t>.json``). The configuration names its
system module (``bench/systems/<s>.py``): how the program's round engine is
built, checked and computed plainly. Each per-layer metric is read by
``bench/metrics/<metric>.py``. A later cell, mix or metric is new files and
new entries; nothing here names one.

Set-up runs from process start to the end of warm-up: TPU start, data,
engine, the checked first rounds (compiles included) and a short timed
call. The window is then one ``RoundEngine.run`` call of as many rounds as
fill ``--seconds`` at the warm-up's round time, ending in
``block_until_ready`` on the parameters. Rounds start where the engine
opens its ``sample`` scope. ``--trace 1`` is a separate run: it traces a
few seconds of rounds with ``jax.profiler`` and prints the per-layer
metrics instead of the end-to-end ones. After the window the program's
state is freed and its first rounds are compared with the plain reference.

The last stdout line is the JSON result. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

_T_IMPORT = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent
TRACE_SECONDS = 8.0  # traced window: a few seconds of steady rounds
WARM_ROUNDS = 5


def add_paths(root: Path) -> None:
    """Make the program (``src/``) and ``bench`` importable."""
    for path in (str(root / "src"), str(root)):
        if path not in sys.path:
            sys.path.insert(0, path)


def init_jax(root: Path, cfg: dict) -> None:
    """The process-wide JAX options of a run: the persistent compile cache
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``, as
    ``repro.launch.compile_cache`` has it; its minimum compile time is left
    at its default) and the configuration's matmul precision, which the
    program states none of its own for: on a TPU the default for float32
    operands is a single bfloat16 pass."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])


def process_age_s() -> float:
    """Seconds since this module was imported, the first thing the process
    does (the clock of ``setup_s``; interpreter start-up before it is some
    tens of milliseconds)."""
    return time.perf_counter() - _T_IMPORT


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a benchmark file that is found by a name, not by import path."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve_cell(root: Path, workload: str) -> dict:
    """The manifest, the cell, its configuration, traffic and system."""
    manifest = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    cfg = load_json(root / configs[cell["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    system = load_module(root / "bench" / "systems" / f"{cfg['system']}.py",
                         f"bench_system_{cfg['system']}")
    return {"manifest": manifest, "cell": cell, "config": cfg,
            "traffic": traffic, "system": system}


def cell_metrics(manifest: dict, workload: str, kind: str) -> list:
    """The cell's metric entries of ``kind`` (end_to_end or per_layer)."""
    return [m for m in manifest[kind]
            if "workloads" not in m or workload in m["workloads"]]


def device_info(chips: int, root: Path) -> tuple:
    """The device record of the result and the chip's peaks.

    Raises :class:`NoAccelerator` off the TPU; a device kind missing from
    ``bench/peaks.json`` is an error, never a default."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks = load_json(root / "bench" / "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} has no row in bench/peaks.json")
    return ({"platform": devs[0].platform, "kind": kind, "count": chips},
            peaks[kind], devs[:chips])


class SpanSink:
    """The engine's ``phase_timers``: timestamps each scope on the host
    clock and marks it in the profiler's trace as well."""

    def __init__(self):
        self.spans = []  # (name, start, end), perf_counter seconds

    @contextlib.contextmanager
    def scope(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield None
            finally:
                self.spans.append((name, t0, time.perf_counter()))


class CompileCounter:
    """Counts backend compiles and persistent-cache hits (``jax.monitoring``).

    A backend-compile event fires for a cache hit too, so compiles are the
    events less the hits."""

    def __init__(self):
        self.events = 0
        self.hits = 0

    def install(self):
        import jax

        def on_duration(event, duration, **kwargs):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events += 1

        def on_event(event, **kwargs):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple:
        return self.events - self.hits, self.hits


def round_times(spans, start: int, t_end: float) -> list:
    """Wall time of each round: from one ``sample`` start to the next, the
    last one to ``t_end``."""
    starts = [s for name, s, _ in spans[start:] if name == "sample"]
    return [b - a for a, b in zip(starts, starts[1:] + [t_end])]


def run_window(engine, sink, rounds: int) -> dict:
    """One ``run`` call of ``rounds`` rounds, ended by ``block_until_ready``."""
    import jax

    mark = len(sink.spans)
    engine.n_rounds = rounds
    t0 = time.perf_counter()
    engine.run()
    jax.block_until_ready(engine.params)
    t1 = time.perf_counter()
    return {"t0": t0, "t1": t1, "mark": mark,
            "rounds": round_times(sink.spans, mark, t1)}


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, dtype=float), q))


def measure(args, cell: dict, root: Path) -> dict:
    """Everything of one run but the printing."""
    stage = {}
    init_jax(root, cell["config"])
    import jax
    import numpy as np

    counter = CompileCounter()
    counter.install()

    device, peaks, devs = device_info(cell["cell"]["chips"], root)
    stage["jax_init_s"] = process_age_s()

    from bench import datagen

    cfg, traffic, system = cell["config"], cell["traffic"], cell["system"]
    data = datagen.make(traffic, args.seed)
    stage["data_s"] = process_age_s() - stage["jax_init_s"]

    sink = SpanSink()
    engine = system.build(cfg, traffic, data, args.seed, sink)
    prog = system.check_steps(engine, traffic["check_rounds"])
    stage["check_s"] = process_age_s() - stage["jax_init_s"] - stage["data_s"]
    warm = run_window(engine, sink, WARM_ROUNDS)
    t_round = float(np.median(warm["rounds"]))
    setup_s = process_age_s()
    compiles0, hits_setup = counter.snapshot()

    target = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    rounds = max(1, int(target / t_round))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        if args.trace:
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation("bench_window"):
                win = run_window(engine, sink, rounds)
            jax.profiler.stop_trace()
        else:
            win = run_window(engine, sink, rounds)
        compiles1, _ = counter.snapshot()
        times = list(win["rounds"])
        window_s = win["t1"] - win["t0"]
        if not args.trace and window_s < 0.9 * target:
            more = run_window(engine, sink, max(1, int(
                (target - window_s) / (window_s / len(times)))))
            times += more["rounds"]
            window_s += more["t1"] - more["t0"]
            compiles1, _ = counter.snapshot()
        evals = sum(1 for name, s, _ in sink.spans[win["mark"]:]
                    if name == "eval")
        finite = all(bool(np.all(np.isfinite(np.asarray(v))))
                     for v in jax.tree_util.tree_leaves(engine.params))
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devs)
        trace_red = None
        if args.trace:
            from bench import tracing

            trace = tracing.load(trace_dir)
            kernel = load_module(root / "bench" / "kernelnames.py",
                                 "bench_kernelnames")
            trace_red = tracing.reduce(trace, kernel.is_uplink_kernel)
            if device["platform"] == "tpu" and trace_red["busy_s"] <= 0:
                raise RuntimeError("the trace holds no operation of a TPU "
                                   "plane in the window")
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    spans = list(sink.spans[win["mark"]:])
    del engine, warm, win
    gc.collect()

    ref = system.reference(cfg, traffic, data, args.seed, traffic["check_rounds"])
    numbers = system.compare(prog, ref, cfg["model"]["lr"])
    limits = cfg["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = finite and all(c["value"] <= c["limit"] for c in checks.values())
    device["memory_peak_bytes"] = int(memory_peak)
    return {
        "setup_s": setup_s, "stage": stage, "setup_cache_hits": hits_setup,
        "setup_compiles": compiles0, "window_compiles": compiles1 - compiles0,
        "rounds": times, "window_s": window_s, "evals": evals,
        "spans": spans, "trace": trace_red, "device": device, "peaks": peaks,
        "work": system.round_work(cfg, traffic), "checks": checks,
        "readings": numbers, "correct": bool(correct), "finite": finite,
        "t_round_warm": t_round,
    }


def end_to_end(m: dict, names: list) -> dict:
    values = {
        "round_s": (m["window_s"] / len(m["rounds"]), "s"),
        "round_p95_s": (percentile(m["rounds"], 95), "s"),
        "setup_s": (m["setup_s"], "s"),
    }
    return {n: {"value": values[n][0], "unit": values[n][1]} for n in names}


def per_layer(m: dict, entries: list, root: Path, workload: str) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing to read returns None and the metric is left out. On the TPU a
    metric whose entry names this cell among its ``workloads`` has to be
    read: its absence there means the trace lost what it reads."""
    ctx = {
        "rounds": len(m["rounds"]), "window_s": m["trace"]["window_s"],
        "spans": m["spans"], "evals": m["evals"],
        "compiles": m["window_compiles"], "trace": m["trace"],
        "work": m["work"], "peaks": m["peaks"],
    }
    out = {}
    for e in entries:
        reader = load_module(root / "bench" / "metrics" / f"{e['name']}.py",
                             f"bench_metric_{e['name']}")
        value = reader.read(ctx)
        if value is not None:
            out[e["name"]] = {"value": float(value), "unit": e["unit"]}
        elif m["device"]["platform"] == "tpu" and workload in e.get(
                "workloads", ()):
            raise RuntimeError(f"{e['name']} found nothing to read in a "
                               f"{workload} trace")
    return out


def main(argv=None, root: Path = ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    add_paths(root)

    cell = resolve_cell(root, args.workload)
    try:
        m = measure(args, cell, root)
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    manifest = cell["manifest"]
    if args.trace:
        metrics = per_layer(m, cell_metrics(manifest, args.workload,
                                            "per_layer"), root, args.workload)
    else:
        metrics = end_to_end(m, [e["name"] for e in cell_metrics(
            manifest, args.workload, "end_to_end")])
    device = dict(m["device"])
    if args.trace:
        device["busy_s"] = m["trace"]["busy_s"]
        device["window_s"] = m["trace"]["window_s"]
    result = {
        "correct": m["correct"],
        "attempted": len(m["rounds"]),
        "failed": 0 if m["finite"] else len(m["rounds"]),
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        result["breakdown"] = {"device_ops": m["trace"]["device_ops"],
                               "idle_gaps": m["trace"]["idle_gaps"]}
    result["checks"] = m["checks"]

    info = {k: m[k] for k in ("stage", "setup_cache_hits", "setup_compiles",
                              "window_compiles", "evals", "t_round_warm",
                              "readings")}
    info["kernel_events"] = m["trace"]["kernel_events"] if m["trace"] else None
    print("bench: " + json.dumps(info), file=sys.stderr)
    for name, c in m["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
