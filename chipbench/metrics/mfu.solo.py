"""Model FLOP/s utilization of the window, in %: operations of every
dispatch of the window, counted from shapes, over the window's host-clock
length times the chip's bf16 peak."""


def read(r):
    return 100.0 * r.flops() / (r.window_s * r.peaks["bf16_flops_per_s"])
