"""Device time of one run of the VAE-decode program, in ms (device
trace); one image per run in the solo cell."""

from chipbench import xplane


def read(r):
    dev = r.device()
    if dev is None:
        return None
    seconds, runs = xplane.program_seconds(dev, r.programs["vae"])
    return 1e3 * seconds / runs if runs else None
