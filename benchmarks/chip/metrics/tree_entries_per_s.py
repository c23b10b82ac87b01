"""Archive entries of every extraction cycle completed in the window
(extracted, committed, removed, drained) over the time from the window's
opening to the last such completion.  A cycle still running at the close
does not count."""


def read(run):
    if not run.cycles:
        return None
    entries = sum(n for _, n in run.cycles)
    return entries / (run.cycles[-1][0] - run.window.t0)
