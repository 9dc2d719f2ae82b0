"""idle_share.serve: share of the traced window in which no operation ran
on the device (1 - busy union / window), in %."""


def compute(rec, tr):
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
