"""Host time of a fit: the mean length of the ``gp.fit`` spans
(``GP.fit``, a ``repro.obs.span``) that lie wholly inside the traced
window, in milliseconds.  The span ends when the fit's programs are
dispatched, not when they finish, so this is the caller's own time: the
indices, the one-hot table's upload and the dispatches."""
from bench.lib import trace

SPAN = "gp.fit"


def read(run):
    if run.trace is None:
        return None
    spans = trace.in_window(run.trace, [e for e in run.trace.host
                                        if e.name == SPAN])
    if not spans:
        return None
    return 1e3 * trace.total_s(spans) / len(spans)
