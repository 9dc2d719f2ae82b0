"""prefill_ms_per_ktok: host time of the window's prompt-completing
prefill chunks (``server.prefill`` spans with ``final``, from planning
the chunk to its first token on the host) per 1,000 tokens they
prefilled, in ms.

It holds only while every prompt fits one chunk.  A chunk that does not
complete its prompt returns before its program has run, so that
program's time is waited out inside whatever waits next (a horizon),
while the final chunk is charged with its own tokens alone.  A window
that holds such a chunk therefore reads None, as does one where the
program keeps no span log (``repro.runtime.tracing``) or the log no
longer holds the window whole.  Host time, not the device time of the
prefill program that the device trace gives."""
from bench import spans


def compute(rec, tr):
    recs = spans.records(rec)
    if not recs:
        return None
    chunks = [r for r in recs if r.name == "server.prefill"
              and "final" in r.counts]
    if not all(r.counts["final"] for r in chunks):
        return None
    tokens = sum(r.counts["tokens"] for r in chunks)
    if not tokens:
        return None
    return 1e6 * sum(r.end - r.start for r in chunks) / tokens
