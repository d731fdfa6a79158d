"""Seconds from process start to the window's first due query."""


def read(run):
    return run.setup_s
