"""Emulated chip-trials completed in the window (fleet size x trials of
every completed call) over the window's wall time, from the start of the
first call to the end of the last call's readback (host clock)."""


def read(run):
    win = run["window"]
    return win["chip_trials"] / win["window_s"]
