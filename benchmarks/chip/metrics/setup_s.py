"""Seconds from the process's start to the window's opening: imports, the
chip, the mount, weights, compilation and the warm-up steps, save and
extraction cycle."""


def read(run):
    return run.setup_s
