"""Device time of the uplink kernel per round of an LM cell.

The sum of the device durations of the kernel's launches in the traced
window (``bench/kernelnames.py`` picks them; a round streams its cohort in
waves, one launch a wave), over the rounds traced. None when the trace
holds no kernel operation, as a trace without a TPU plane does."""


def read(ctx):
    t = ctx["trace"]
    if not t["kernel_events"] or not ctx["rounds"]:
        return None
    return 1e3 * t["kernel_s"] / ctx["rounds"]
