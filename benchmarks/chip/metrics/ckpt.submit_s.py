"""Host seconds a save of the window spent in the manager's ``ckpt.submit``
spans: the step directory, the manifest, and each leaf's open, chunked
writes and close handed to the engine until they are acknowledged."""
import hostspans


def read(run):
    return hostspans.s_per_save(run, "ckpt.submit")
