"""The program's span log (``repro.runtime.tracing``) over a run's
window, for the readers of ``program_span`` and ``program_counter``
metrics."""


def records(rec):
    """The span records wholly inside ``rec["window"]``; None where the
    program keeps no span log or the log no longer holds the window
    whole."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    return tracing.records(*rec["window"])
