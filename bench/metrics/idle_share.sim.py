"""Device idle share of an engine cell's window, from the profiler
trace: 1 - (union of device op intervals) / window, in %."""
from bench.trace import idle_share


def read(window: dict):
    return idle_share(window)
