"""Grouped sums the fused vectorized operator handed to the device
(``kernel_calls + x64_sums`` of the scheduler's device counters) per
completed query."""


def read(run):
    d = run["device_stats"]
    if not run["queries"]:
        return None
    return (d.get("kernel_calls", 0) + d.get("x64_sums", 0)) / run["queries"]
