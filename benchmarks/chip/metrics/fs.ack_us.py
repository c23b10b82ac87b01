"""Mean caller-visible microseconds of a submission to the engine in the
window: the growth of its counters, ack_latency_s over eager_acks plus
sync_ops.  The engine adds to ack_latency_s both the wait of an eager
operation for its acknowledgement and that of a synchronous one for its
completion, so both are counted below the line too.  Read as
``fs.ack_us.ckpt`` in the cells that save and as ``fs.ack_us.tree`` in
those that extract, since it moves another end-to-end metric in each."""


def read(run):
    ops = run.stats.get("eager_acks", 0) + run.stats.get("sync_ops", 0)
    if not ops:
        return None
    return 1e6 * run.stats["ack_latency_s"] / ops
