"""Set-up seconds on the host clock: imports, device discovery, the build,
state from the seed on the device and one warm-up call (with compilation or
the compile cache's reading), up to the start of the window."""


def read(run):
    return run["setup_s"]
