"""What a pay-per-use user pays per query: the CostLedger's total over the
window (Lambda, SQS and S3 at the paper's 2018 prices) per completed
query."""


def read(run):
    if not run["queries"]:
        return None
    return run["counters"]["total_usd"] / run["queries"]
