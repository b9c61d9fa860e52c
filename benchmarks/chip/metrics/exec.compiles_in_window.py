"""Backend compiles (persistent-cache reads included) between the
window's opening and its close. Should read 0."""


def read(run):
    return run.compiles_in_window
