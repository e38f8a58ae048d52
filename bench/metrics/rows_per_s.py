"""Table rows covered by every completed query, over the window's
seconds (from its start to its last answer)."""


def read(run):
    return run["rows_scanned"] / run["window_s"] if run["queries"] else None
