"""The uplink kernel's share of its HBM roofline in an LM cell.

The least time the bytes the work needs (``bench.refmath.uplink_bytes``:
each client's payload read once, the f32 aggregate written once, a 4-byte
counter per client) could take at the chip's HBM bandwidth, over the
kernel's measured device time. The running aggregate each wave reads back
is not work, so it is not counted. None without kernel events."""


def read(ctx):
    t = ctx["trace"]
    if not t["kernel_events"] or t["kernel_s"] <= 0:
        return None
    least_s = (ctx["work"]["uplink_bytes"] * ctx["rounds"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / t["kernel_s"]
