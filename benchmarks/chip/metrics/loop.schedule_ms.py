"""Host milliseconds a step of the window spent in the loop's
``train.schedule`` span: the learning rate's eager ops and the step
counter's scalar."""
import hostspans


def read(run):
    return hostspans.ms_per_step(run, "train.schedule")
