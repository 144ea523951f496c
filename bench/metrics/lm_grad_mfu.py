"""The gradient's share of the chip's bf16 peak in an LM cell.

Operations the clients' forward and backward passes need each round
(``bench.refmath_moonlight.work``: from shapes, each held expert at its
routed share of the tokens) times the rounds traced, over the device busy
time outside the uplink kernel, over the peak of ``bench/peaks.json``. The
busy time left holds the gradient and the little else a round runs (the
flatten, the gather, the apply). None when the trace holds no device
operation or no kernel, as a trace without a TPU plane does."""


def read(ctx):
    t = ctx["trace"]
    busy = t["busy_s"] - t["kernel_s"]
    if not t["kernel_events"] or busy <= 0 or not ctx["rounds"]:
        return None
    ops = ctx["work"]["train_flops"] * ctx["rounds"]
    return 100.0 * ops / busy / ctx["peaks"]["bf16_flops_per_s"]
