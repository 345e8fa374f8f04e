"""Device time of one FLUX single-stream block per backbone row per
denoising step, in ms (device trace): the ``single_stream`` scan of the
DenoiseSegment program is the ``while`` op whose carried state holds the
joined [text; image] sequence [rows, tokens, d_model]; its time over the
geometry's single blocks, rows per step from the cell's architecture and
request-steps from the dispatch log."""

from chipbench import xplane


def read(r):
    g, dev, steps = r.geometry, r.device(), r.request_steps()
    blocks = getattr(g, "n_single", 0)
    if dev is None or not steps or not blocks:
        return None
    loop = rf"^%while[\w.\-]* = \(.*\w+\[\d+,{g.tokens},{g.d_model}\]"
    seconds, runs = xplane.op_seconds(dev, loop, r.programs["segment"])
    if not runs:
        return None
    return 1e3 * seconds / (blocks * r.rows_per_step * steps)
