"""Limb columns a grouped-sum device program summed on average:
``device_limb_columns`` / (``kernel_calls`` + ``x64_sums``) of the
scheduler's counters (an x64 program counts one limb a column). None
where no program ran or the program has no such counter."""


def read(run):
    d = run["device_stats"]
    programs = d.get("kernel_calls", 0) + d.get("x64_sums", 0)
    if not programs or "device_limb_columns" not in d:
        return None
    return d["device_limb_columns"] / programs
