"""Mean microseconds an operation the engine executed in the window sat
ready in its queue before a worker started it: the growth of queue_wait_s
over that of executed."""


def read(run):
    ops = run.stats.get("executed", 0)
    if "queue_wait_s" not in run.stats or not ops:
        return None
    return 1e6 * run.stats["queue_wait_s"] / ops
