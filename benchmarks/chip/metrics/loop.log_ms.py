"""Host milliseconds a step of the window spent in the loop's ``train.log``
span: the step's metrics brought to the host and written through the
engine."""
import hostspans


def read(run):
    return hostspans.ms_per_step(run, "train.log")
