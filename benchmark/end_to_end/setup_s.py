"""Process start to the first measured call: loading, the native build
and compilation on a first run, warming the cell's shapes."""


def read(run):
    return run.setup_s
