"""Programs the device runs per serving call: the ``XLA Modules`` events
(one per program run) of the first device in the traced window, over the
``gp.mean_var`` spans (``GP.mean_var``, a ``repro.obs.span``) wholly
inside it.  One jitted call would read 1; every eager ``jnp`` operation on
the call's path adds a program."""
from bench.lib import trace

SPAN = "gp.mean_var"


def read(run):
    tr = run.trace
    if tr is None or not tr.modules:
        return None
    spans = trace.in_window(tr, [e for e in tr.host if e.name == SPAN])
    if not spans:
        return None
    programs = trace.in_window(tr, tr.modules[min(tr.modules)])
    return len(programs) / len(spans)
