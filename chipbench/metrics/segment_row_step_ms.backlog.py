"""Device time of the DenoiseSegment program per backbone row per
denoising step, in ms (device trace; steps from the dispatch log, rows
per step from the cell's architecture)."""

from chipbench import xplane


def read(r):
    dev, steps = r.device(), r.request_steps()
    if dev is None or not steps:
        return None
    seconds, runs = xplane.program_seconds(dev, r.programs["segment"])
    return 1e3 * seconds / (r.rows_per_step * steps) if runs else None
