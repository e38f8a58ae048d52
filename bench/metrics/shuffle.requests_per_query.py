"""Billed transport requests (SQS requests, S3 GETs, PUTs and LISTs) per
completed query, from the CostLedger's growth over the window."""


def read(run):
    if not run["queries"]:
        return None
    led = run["counters"]
    return (led["sqs_requests"] + led["s3_gets"] + led["s3_puts"]
            + led["s3_lists"]) / run["queries"]
