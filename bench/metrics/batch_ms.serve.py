"""Mean host wall time of one gateway dispatch (pad, copy in, the
blocked bucket executable, copy out), from ServeMonitor's per-bucket
wall_us over its batch count, across the window."""


def read(layer):
    a, b = layer.quantities.get("monitor_after"), \
        layer.quantities.get("monitor_before")
    if not a:
        return None
    batches = a.get("batches", 0) - b.get("batches", 0)
    if batches <= 0:
        return None
    wall_us = sum(v.get("wall_us", 0) for v in a["buckets"].values()) - \
        sum(v.get("wall_us", 0) for v in b["buckets"].values())
    return wall_us / batches / 1e3
