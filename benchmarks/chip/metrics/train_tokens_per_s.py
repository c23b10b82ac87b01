"""Tokens of every step completed in the window over the window's seconds.

The window opens at a step's call and closes at a step's call, after the
previous step's ``block_until_ready`` and whatever the loop did after it
(a save, where one was due)."""


def read(run):
    return len(run.steps) * run.tokens_per_step / run.window_s
