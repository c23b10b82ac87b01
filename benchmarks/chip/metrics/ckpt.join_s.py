"""Host seconds a save of the window spent in the manager's ``ckpt.join``
span: waiting for the previous save's commit before starting."""
import hostspans


def read(run):
    return hostspans.s_per_save(run, "ckpt.join")
