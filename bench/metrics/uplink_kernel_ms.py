"""Device time of the uplink kernel per round.

The sum of the device durations of the kernel's operations in the traced
window (``bench/kernelnames.py`` picks them), over the rounds traced. None
when the trace holds no kernel operation."""


def read(ctx):
    t = ctx["trace"]
    if not t["kernel_events"] or not ctx["rounds"]:
        return None
    return 1e3 * t["kernel_s"] / ctx["rounds"]
