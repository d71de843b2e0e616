"""Model FLOP utilisation of the whole serving step: the operations the
model needs for every prefill and decode token computed in the window
(`Run.window_flops`, by the family's `forward_flops`) over the window
times the chip's bf16 peak."""


def read(run):
    flops = run.window_flops()
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peaks["bf16_flops_per_s"])
