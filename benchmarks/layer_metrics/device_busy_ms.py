"""operators: milliseconds a query keeps the device busy: the union of device-op intervals in the
traced window, over its queries."""


def read(run):
    if run.trace is None or not run.trace.queries or not run.trace.busy_s:
        return None
    return run.trace.busy_s * 1e3 / run.trace.queries
