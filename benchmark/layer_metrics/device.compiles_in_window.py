"""First calls (compilations) the program counted between the window's two
snapshots: compile_snapshot() delta. Has to read 0."""


def read(run):
    return run["delta"]["compiles"]
