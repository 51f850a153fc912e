"""edges_per_batch: edges the fused driver processed (stats.edges_processed:
in-edges of active row blocks plus out-edges of changed column blocks),
mean over the window's update batches."""


def read(run):
    b = run["batches"]
    if run["loop"] != "closed" or not b:
        return None
    return sum(x["edges"] for x in b) / len(b)
