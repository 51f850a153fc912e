"""host_s_per_batch: the part of each ``update`` call (the benchmark's
``bench.update`` span) in which the device ran no operation: the session's
host path (validation, delta planning, seeding), mean per batch."""


def read(run):
    tr = run["trace"]
    if tr is None or run["loop"] != "closed":
        return None
    spans = tr.spans.get("bench.update", [])
    if not spans:
        return None
    return sum(tr.device_idle_in(lo, hi) for lo, hi in spans) / len(spans)
