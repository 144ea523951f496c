"""Programs compiled inside the window, per round.

Backend compiles less persistent-cache hits, from ``jax.monitoring``
events. Once warm, the round engine's jitted round should compile nothing:
a count here means the engine traces a new shape or a new program inside
the measured rounds."""


def read(ctx):
    return ctx["compiles"] / ctx["rounds"] if ctx["rounds"] else None
