"""Set-up: weights, tables, compiles and warm traffic, before the window
(host clock)."""


def read(run):
    return run.setup_s
