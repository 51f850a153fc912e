"""sweeps_per_dispatch: the fused driver's sweep count (stats.sweeps),
mean over the service's dispatches in the window."""


def read(run):
    b = run["batches"]
    if run["loop"] != "open" or not b:
        return None
    return sum(x["sweeps"] for x in b) / len(b)
