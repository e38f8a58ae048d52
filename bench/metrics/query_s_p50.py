"""Median seconds from submit to collected result over every query that
completed in the window."""

import statistics


def read(run):
    lat = run["latencies"]
    return statistics.median(lat) if lat else None
