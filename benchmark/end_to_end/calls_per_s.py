"""Verified calls completed inside the window, per second of it."""


def read(run):
    if not run.in_window or run.window_s <= 0:
        return None
    return run.in_window / run.window_s
