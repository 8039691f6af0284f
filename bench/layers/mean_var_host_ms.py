"""Host time of a serving call: the mean length of the ``gp.mean_var``
spans (``GP.mean_var``, a ``repro.obs.span``) that lie wholly inside the
traced window, in milliseconds.  The span ends when the call's programs
are dispatched, not when they finish, so this is the caller's own time:
the features' constants, their dispatch and the solve's dispatch."""
from bench.lib import trace

SPAN = "gp.mean_var"


def read(run):
    if run.trace is None:
        return None
    spans = trace.in_window(run.trace, [e for e in run.trace.host
                                        if e.name == SPAN])
    if not spans:
        return None
    return 1e3 * trace.total_s(spans) / len(spans)
