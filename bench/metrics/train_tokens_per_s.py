"""Tokens of the optimizer steps completed in the window, over the
window (host clock, ended by the last step's result)."""


def read(run):
    return run.values["tokens_per_s"]
