"""The program's own host spans (``repro.trace``) that lie in a run's
window, for the readers under ``metrics/``.  Each function returns None
where there is nothing to divide by, or where the program records no spans
(one older than the recorder)."""
from __future__ import annotations


def seconds(run, name: str) -> float | None:
    """Total seconds of the spans called ``name`` wholly in the window."""
    try:
        from repro.trace import spans
    except ImportError:
        return None
    return sum(s.t1 - s.t0
               for s in spans(name, run.window.t0, run.window.t1))


def ms_per_step(run, name: str) -> float | None:
    total = seconds(run, name)
    if total is None or not run.steps:
        return None
    return 1e3 * total / len(run.steps)


def s_per_save(run, name: str) -> float | None:
    total = seconds(run, name)
    if total is None or not run.saves:
        return None
    return total / len(run.saves)
