"""Device program executions in the traced window per query answered."""


def read(run):
    if run.trace is None or run.trace.launches == 0:
        return None
    return run.trace.launches / run.n_queries
