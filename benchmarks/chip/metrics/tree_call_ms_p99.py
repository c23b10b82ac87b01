"""99th percentile (nearest rank) of the caller-side milliseconds of every
fs call the extractor made in the window."""
import math


def p99(samples):
    if not samples:
        return None
    s = sorted(samples)
    return s[math.ceil(0.99 * len(s)) - 1]


def read(run):
    return p99(run.call_ms)
