"""Mean host seconds the loop was kept from calling the next step after a
step that saved: from that step's return to the next step's call."""


def read(run):
    if not run.save_steps:
        return None
    return sum(run.gap_after(k) for k in run.save_steps) / len(run.save_steps)
