"""Device milliseconds of the grouped sum's programs (``_kernel_sums``,
``_x64_sums``) in the traced window, per completed query."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["grouped_sum_programs"] or not run["queries"]:
        return None
    return tr["grouped_sum_s"] * 1e3 / run["queries"]
