"""Host milliseconds a step of the window spent in the loop's ``train.batch``
span: the next batch from the data iterator and its ``put_batch`` onto the
device."""
import hostspans


def read(run):
    return hostspans.ms_per_step(run, "train.batch")
