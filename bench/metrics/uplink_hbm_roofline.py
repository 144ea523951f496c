"""The uplink kernel's share of its HBM roofline.

The least time the bytes the work needs could take at the chip's HBM
bandwidth, over the kernel's measured device time. The kernel uses no MXU,
so bytes bound it, not operations. The bytes (``bench.refmath.uplink_bytes``)
are each client's payload read once at its wire width, the f32 aggregate
written once and one 4-byte counter per client: no padding, no masked rows,
nothing that depends on how the uplink is implemented."""


def read(ctx):
    t = ctx["trace"]
    if not t["kernel_events"] or t["kernel_s"] <= 0:
        return None
    least_s = (ctx["work"]["uplink_bytes"] * ctx["rounds"]
               / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s / t["kernel_s"]
