"""The flash-attention kernel's share of its roofline inside the
DenoiseSegment program, in %: the least time the chip needs for the
unpadded QK^T and PV work of every joint attention call of the window
(the larger of FLOPs over the bf16 peak and q, k, v, o bytes over HBM
bandwidth), over the kernel's device time (device trace)."""

from chipbench import flops, xplane


def read(r):
    dev, steps = r.device(), r.request_steps()
    if dev is None or not steps:
        return None
    seconds, calls = xplane.op_seconds(dev, r.flash_kernel,
                                       r.programs["segment"])
    if not calls:
        return None
    g, p = r.geometry, r.peaks
    per_call = flops.roofline_seconds(
        flops.flash_attn_flops(g), flops.flash_attn_bytes(g),
        p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    return 100.0 * per_call * 2 * steps * g.n_layers / seconds
