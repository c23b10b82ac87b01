"""Host seconds a save of the window spent in the manager's
``ckpt.serialize`` spans: flattening the state and each leaf's
``tobytes``."""
import hostspans


def read(run):
    return hostspans.s_per_save(run, "ckpt.serialize")
