"""Host seconds a save of the window spent in the loop's
``train.fetch_state`` span: the device-to-host copy of the training state
before ``save()`` is called."""
import hostspans


def read(run):
    return hostspans.s_per_save(run, "train.fetch_state")
