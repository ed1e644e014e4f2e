"""Programs the runtime's engine compiled inside the window (should be
0: every shape is warmed in set-up)."""


def read(run):
    return run.values["window_compiles"]
