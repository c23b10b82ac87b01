"""Mean host milliseconds from a step's return to the next step's call,
over the window's steps that did not save (the last step, whose next call
closes the window, included)."""


def read(run):
    steps = [k for k in run.steps if k not in run.save_steps]
    if not steps:
        return None
    return 1e3 * sum(run.gap_after(k) for k in steps) / len(steps)
