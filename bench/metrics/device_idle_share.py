"""Share of the traced window in which no operation ran on the device.

One minus the union of the device operations' intervals over the window
(``bench.tracing.reduce``), averaged over the chips used."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
