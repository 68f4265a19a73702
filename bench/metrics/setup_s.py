"""End to end: process start to the window's start, in seconds (weights,
compiles or loads from the cache, warm steps)."""


def read(run):
    return run["setup_s"]
