"""Billed Lambda GB-seconds (task durations in 100 ms slices times memory)
per completed query, from the CostLedger's growth over the window."""


def read(run):
    if not run["queries"]:
        return None
    return run["counters"]["lambda_gb_seconds"] / run["queries"]
