"""Device milliseconds inside the compiled ``serve_many`` per serve step,
from the program's module events in the trace."""


def read(run):
    steps = sum(d.steps for d in run.traced)
    if not run.trace or run.trace["module_s"] <= 0 or not steps:
        return None
    return 1e3 * run.trace["module_s"] / steps
