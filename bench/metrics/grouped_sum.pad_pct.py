"""Percent of the rows the grouped sum's device programs summed that
were padding: 100 x (padded rows - rows) / padded rows, from the
scheduler's ``device_rows`` and ``device_padded_rows`` counters."""


def read(run):
    d = run["device_stats"]
    padded = d.get("device_padded_rows", 0)
    if not padded:
        return None
    return 100.0 * (padded - d.get("device_rows", 0)) / padded
