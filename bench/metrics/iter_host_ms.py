"""iter_host_ms: host time of one scheduler iteration that the device
waits through, in ms — the mean, over the window's
``scheduler.iteration`` spans, of each span less its ``server.*.wait``
descendants (the program's span log, ``repro.runtime.tracing``).  None
where the program keeps no span log, or the log no longer holds the
window whole."""
from bench import spans


def compute(rec, tr):
    recs = spans.records(rec)
    if not recs:
        return None
    by_seq = {r.seq: r for r in recs}
    waited = {}
    for r in recs:
        if r.name.startswith("server.") and r.name.endswith(".wait"):
            p = r.parent
            while p in by_seq and by_seq[p].name != "scheduler.iteration":
                p = by_seq[p].parent
            if p in by_seq:
                waited[p] = waited.get(p, 0.0) + r.end - r.start
    its = [r for r in recs if r.name == "scheduler.iteration"]
    if not its:
        return None
    return 1e3 * sum(r.end - r.start - waited.get(r.seq, 0.0)
                     for r in its) / len(its)
