"""operators: program executions on the device in the traced window, over its queries."""


def read(run):
    if run.trace is None or not run.trace.queries or not run.trace.launches:
        return None
    return run.trace.launches / run.trace.queries
