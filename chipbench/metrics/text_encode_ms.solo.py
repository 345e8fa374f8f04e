"""Device time of one run of the text-encoder program, in ms (device
trace); one prompt per run in the solo cell."""

from chipbench import xplane


def read(r):
    dev = r.device()
    if dev is None:
        return None
    seconds, runs = xplane.program_seconds(dev, r.programs["text_encoder"])
    return 1e3 * seconds / runs if runs else None
