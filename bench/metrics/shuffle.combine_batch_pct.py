"""Percent of the map-side combine's records that the shuffle writer
folded column-wise from grouped partials: 100 x combine_batch_records /
(combine_batch_records + combine_row_records) of the scheduler's
counters. None where neither was counted, as in a program without
them."""


def read(run):
    d = run["device_stats"]
    batch = d.get("combine_batch_records", 0)
    records = batch + d.get("combine_row_records", 0)
    if not records:
        return None
    return 100.0 * batch / records
