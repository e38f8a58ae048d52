"""Percent of the fused operator's chunks re-run on the row path:
100 x fused_row_fallback_chunks / fused_chunks of the scheduler's
counters. None where no chunk was counted, as in a program without
them."""


def read(run):
    d = run["device_stats"]
    chunks = d.get("fused_chunks", 0)
    if not chunks:
        return None
    return 100.0 * d.get("fused_row_fallback_chunks", 0) / chunks
