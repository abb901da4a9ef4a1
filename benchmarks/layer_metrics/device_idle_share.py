"""Device: 1 - (union of the intervals in which an operation ran on the
device) / (traced interval), averaged over the chips used, in %."""


def read(art):
    t = art["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
