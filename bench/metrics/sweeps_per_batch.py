"""sweeps_per_batch: the fused driver's sweep count (stats.sweeps), mean
over the window's update batches."""


def read(run):
    b = run["batches"]
    if run["loop"] != "closed" or not b:
        return None
    return sum(x["sweeps"] for x in b) / len(b)
