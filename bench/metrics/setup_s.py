"""setup_s: seconds from process start to the window's start (graph,
session build and its initial solve, compilation or cache loads, warm-up)."""


def read(run):
    return run["setup_s"]
