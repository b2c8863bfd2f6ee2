"""1 - busy / window of the device in the traced window, where busy is
the union of the device op intervals (averaged over the chips used)."""
from bench.harness.readers import idle_share


def read(run):
    return idle_share(run)
