"""Percent of the scan's CSV chunks that the fused operator parsed
column-wise from one byte buffer: 100 x ingest_columnar_chunks /
(ingest_columnar_chunks + ingest_line_chunks) of the scheduler's
counters. None where neither was counted, as in a program without
them."""


def read(run):
    d = run["device_stats"]
    columnar = d.get("ingest_columnar_chunks", 0)
    chunks = columnar + d.get("ingest_line_chunks", 0)
    if not chunks:
        return None
    return 100.0 * columnar / chunks
