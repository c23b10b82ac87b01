"""Backend operations the engine executed per operation submitted to it in
the window (its counters' growth: executed over submitted); fusion and
elision bring it under 1."""


def read(run):
    sub = run.stats.get("submitted", 0)
    if not sub:
        return None
    return run.stats["executed"] / sub
