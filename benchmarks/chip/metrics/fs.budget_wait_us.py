"""Mean microseconds a submission to the engine in the window spent
blocked on the in-flight budget (``max_inflight``): the growth of
budget_wait_s over that of eager_acks plus sync_ops, the same
denominator as ``fs.ack_us``.  Read as ``fs.budget_wait_us.ckpt`` in the
cells that save and ``fs.budget_wait_us.tree`` in those that extract."""


def read(run):
    ops = run.stats.get("eager_acks", 0) + run.stats.get("sync_ops", 0)
    if "budget_wait_s" not in run.stats or not ops:
        return None
    return 1e6 * run.stats["budget_wait_s"] / ops
