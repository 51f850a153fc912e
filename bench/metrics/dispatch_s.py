"""dispatch_s: mean seconds of one service dispatch (done_s - started_s:
the coalesced update), over the window's dispatches."""


def read(run):
    b = run["batches"]
    if run["loop"] != "open" or not b:
        return None
    return sum(x["end"] - x["start"] for x in b) / len(b)
