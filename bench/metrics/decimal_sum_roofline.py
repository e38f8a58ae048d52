"""Share of the HBM roofline the decimal grouped sums reached: the least
time the chip needs to move the mix's ``sum_bytes`` at its peak, over
the device time of the grouped sum's programs in the traced window, in
percent."""


def read(run):
    tr = run["trace"]
    if (not tr or not tr["grouped_sum_programs"] or not run["peaks"]
            or not run["grouped_sum_bytes"]):
        return None
    least_s = run["grouped_sum_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["grouped_sum_s"]
