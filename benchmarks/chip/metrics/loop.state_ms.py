"""Host milliseconds a step of the window spent in the loop's
``train.state`` span: the step's outputs put in place of the previous
state, whose arrays are freed there."""
import hostspans


def read(run):
    return hostspans.ms_per_step(run, "train.state")
