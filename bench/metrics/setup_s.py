"""setup_s: seconds from the start of set-up (weights or data from the
seed, server, every program the cell can reach, a warm request) to the
start of the arrival schedule."""


def compute(rec, tr):
    return rec["setup_s"]
