"""Share of dispatched rows that were padding: ServeMonitor's pad_rows
over rows plus pad_rows, counted across the window."""


def read(layer):
    a, b = layer.quantities.get("monitor_after"), \
        layer.quantities.get("monitor_before")
    if not a:
        return None
    rows = a.get("rows", 0) - b.get("rows", 0)
    pad = a.get("pad_rows", 0) - b.get("pad_rows", 0)
    if rows + pad <= 0:
        return None
    return 100.0 * pad / (rows + pad)
