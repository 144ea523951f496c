"""The reduction from a trace to busy time, idle gaps and kernel time, on a
small synthetic trace, and the reading of a real (CPU) trace's host spans."""

import glob

import pytest

from bench import kernelnames, tracing
from bench.tracing import Event, Trace


def _trace():
    ops = [
        Event("custom-call.1", 10, 20, {"hlo_op": "tpu_custom_call"}),
        Event("fusion.2", 15, 30),
        Event("fusion.2", 50, 60),
        Event("copy.3", 95, 110),  # runs past the window's end
        Event("fusion.9", 200, 300),  # after the window
    ]
    spans = [Event("bench_window", 0, 100), Event("sample", 0, 10),
             Event("round", 10, 45), Event("telemetry", 45, 70),
             Event("eval", 70, 100)]
    return Trace(ops, spans, 1)


@pytest.mark.parametrize("name, is_kernel", [
    ('%approx_channel_batch_aggregate_pallas.1 = (f32[176,128]{1,0}, '
     's32[22,8,128]{2,1,0}) custom-call(f32[100]{0} %broadcast.81, '
     'u32[100,176,128]{2,1,0} %slice.29), '
     'custom_call_target="tpu_custom_call"', True),
    ('%get-tuple-element.7 = f32[176,128]{1,0} get-tuple-element('
     '(f32[176,128]{1,0}, s32[22,8,128]{2,1,0}) '
     '%approx_channel_batch_aggregate_pallas.1), index=0', False),
    ('%fusion.1 = f32[100,10,1,5,5]{1,2,0,4,3} fusion(f32[100,32,1,28,28]'
     '{1,2,0,4,3} %copy-done), kind=kOutput, calls=%fused_computation.1',
     False),
])
def test_the_kernel_is_matched_by_its_own_name_not_its_operands(name,
                                                                is_kernel):
    # Event names as a TPU v5e trace gives them: the HLO text of the op.
    assert kernelnames.is_uplink_kernel(Event(name, 0, 1)) is is_kernel


def test_union_merges_overlaps():
    assert tracing.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [[0, 3], [5, 9]]


def test_busy_is_the_union_inside_the_window():
    red = tracing.reduce(_trace(), kernelnames.is_uplink_kernel)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(35e-9)  # [10,30] [50,60] [95,100]
    assert red["idle_share"] == pytest.approx(0.65)


def test_kernel_time_sums_only_the_kernels_events():
    red = tracing.reduce(_trace(), kernelnames.is_uplink_kernel)
    assert red["kernel_s"] == pytest.approx(10e-9)
    assert red["kernel_events"] == 1


def test_top_ops_are_clipped_to_the_window():
    red = tracing.reduce(_trace(), kernelnames.is_uplink_kernel)
    ops = dict(red["device_ops"])
    assert ops["fusion.2"] == pytest.approx(25e-9)
    assert ops["copy.3"] == pytest.approx(5e-9)
    assert "fusion.9" not in ops


def test_idle_gaps_are_named_by_the_host_span_that_overlaps_most():
    red = tracing.reduce(_trace(), kernelnames.is_uplink_kernel)
    assert [g[0] for g in red["idle_gaps"]] == ["eval", "round", "sample"]
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [35e-9, 20e-9, 10e-9])


def test_a_trace_without_a_window_is_refused():
    t = _trace()
    t.spans = [s for s in t.spans if s.name != "bench_window"]
    with pytest.raises(ValueError):
        tracing.reduce(t, kernelnames.is_uplink_kernel)


def test_load_reads_the_host_spans_of_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sum(x * 2.0))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench_window"):
        for name in tracing.HOST_SPANS:
            with jax.profiler.TraceAnnotation(name):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    trace = tracing.load(str(tmp_path))
    names = sorted(s.name for s in trace.spans)
    assert names == sorted(("bench_window",) + tracing.HOST_SPANS)
    lo, hi = tracing.window(trace)
    assert all(lo <= s.start and s.end <= hi for s in trace.spans)


def _metric_inputs(platform, kernel_events):
    from bench import run

    red = tracing.reduce(_trace(), kernelnames.is_uplink_kernel)
    red["kernel_events"] = kernel_events
    m = {"rounds": [0.1] * 4, "spans": [("sample", 0.0, 0.01)], "evals": 0,
         "window_compiles": 0, "trace": red,
         "work": {"train_flops": 1.0, "eval_flops": 0.0, "uplink_bytes": 1.0},
         "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
         "device": {"platform": platform}}
    entries = [e for e in run.load_json(run.ROOT / "BENCHMARK.json")["per_layer"]
               if e["name"] == "uplink_kernel_ms"]
    return run, m, entries, entries[0]["workloads"][0]


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_the_kernel_metric_is_read_where_the_trace_has_the_kernel(platform):
    run, m, entries, cell = _metric_inputs(platform, kernel_events=1)
    out = run.per_layer(m, entries, run.ROOT, cell)
    assert out["uplink_kernel_ms"]["value"] > 0


def test_a_tpu_trace_without_the_kernel_fails_the_run():
    run, m, entries, cell = _metric_inputs("tpu", kernel_events=0)
    with pytest.raises(RuntimeError, match="uplink_kernel_ms"):
        run.per_layer(m, entries, run.ROOT, cell)


def test_a_cpu_trace_without_the_kernel_leaves_the_metric_out():
    run, m, entries, cell = _metric_inputs("cpu", kernel_events=0)
    assert run.per_layer(m, entries, run.ROOT, cell) == {}
