"""The flash-attention kernel's share of its roofline inside the
DenoiseSegment program, in %: the least time the chip needs for the
unpadded work of every attention call of the window (for each call the
cell's architecture counts in a backbone row-step, the larger of its
FLOPs over the bf16 peak and its q, k, v, o bytes over HBM bandwidth),
over the kernel's device time (device trace)."""

from chipbench import flops, xplane


def read(r):
    dev, steps = r.device(), r.request_steps()
    if dev is None or not steps:
        return None
    seconds, calls = xplane.op_seconds(dev, r.flash_kernel,
                                       r.programs["segment"])
    if not calls:
        return None
    p = r.peaks
    row_step = sum(flops.roofline_seconds(f, b, p["bf16_flops_per_s"],
                                          p["hbm_bytes_per_s"])
                   for f, b in r.attention_calls)
    return 100.0 * row_step * r.rows_per_step * steps / seconds
