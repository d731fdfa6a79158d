"""Queries answered per second of the window (host clock): all that the
window's flushes answered, the one under way at its close included, over
the time from its start to that flush's end."""


def read(run):
    return run.completed_in_window / run.window_s
