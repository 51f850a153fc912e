"""update_s: the window's time, from the first batch's start to the last
batch's completion, divided by the batches completed (closed loop)."""


def read(run):
    b = run["batches"]
    if run["loop"] != "closed" or not b:
        return None
    return (b[-1]["end"] - b[0]["start"]) / len(b)
