"""Share of the HBM roofline that the codec's device time reaches: the
least time the geometry allows (data x n / k bytes at the chip's peak
HBM rate) over the device's busy time in the window.  It counts the
geometry, not the kernels, so a later kernel that fuses, renames or
replaces today's is held to the same yardstick."""


def read(run):
    t, work = run.trace, run.work
    if not t or not run.peaks or t["busy_s"] <= 0 or not work.get("calls"):
        return None
    least_s = work["hbm_min_bytes"] / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / t["busy_s"]
