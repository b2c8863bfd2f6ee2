"""Window wall time over the closed-loop trials completed in it, in
microseconds (host clock): compares with the silicon's 290 us per Section 5
training step."""


def read(run):
    win = run["window"]
    return win["window_s"] / win["trials"] * 1e6
