"""Device time of one FLUX double-stream block per backbone row per
denoising step, in ms (device trace): the ``double_stream`` scan of the
DenoiseSegment program is the ``while`` op whose carried state holds the
image stream [rows, image_tokens, d_model] and the text stream [rows,
text_tokens, d_model] apart (and not the joined sequence); its time over
the geometry's double blocks, rows per step from the cell's
architecture and request-steps from the dispatch log."""

from chipbench import xplane


def read(r):
    g, dev, steps = r.geometry, r.device(), r.request_steps()
    blocks = getattr(g, "n_double", 0)
    if dev is None or not steps or not blocks:
        return None
    shape = lambda tokens: rf"\w+\[\d+,{tokens},{g.d_model}\]"
    loop = (rf"^%while[\w.\-]* = \((?=.*{shape(g.image_tokens)})"
            rf"(?=.*{shape(g.text_tokens)})(?!.*{shape(g.tokens)})")
    seconds, runs = xplane.op_seconds(dev, loop, r.programs["segment"])
    if not runs:
        return None
    return 1e3 * seconds / (blocks * r.rows_per_step * steps)
