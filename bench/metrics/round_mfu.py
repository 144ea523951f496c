"""The whole round's share of the chip's bf16 peak.

Operations the rounds need (the CNN's forward and backward for every
client's minibatch, and the forward over the evaluation set on evaluated
rounds, counted from shapes by ``bench.refmath``) over the traced window's
time and the peak of ``bench/peaks.json``."""


def read(ctx):
    work = ctx["work"]
    ops = work["train_flops"] * ctx["rounds"] + work["eval_flops"] * ctx["evals"]
    if ctx["window_s"] <= 0:
        return None
    return 100.0 * ops / ctx["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
