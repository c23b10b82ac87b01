"""Mean microseconds a synchronous submission to the engine in the window
waited for its completion: the growth of sync_wait_s over that of
sync_ops."""


def read(run):
    ops = run.stats.get("sync_ops", 0)
    if "sync_wait_s" not in run.stats or not ops:
        return None
    return 1e6 * run.stats["sync_wait_s"] / ops
