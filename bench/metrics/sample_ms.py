"""Host time the round engine spends in its ``sample`` scope, per round.

The scope gathers each client's minibatch with numpy and moves it to the
device (``FedSGD.sample``). Read from the harness's span sink."""


def read(ctx):
    spans = [e - s for name, s, e in ctx["spans"] if name == "sample"]
    return 1e3 * sum(spans) / len(spans) if spans else None
