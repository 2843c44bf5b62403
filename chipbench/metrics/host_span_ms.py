"""Mean milliseconds of one of the benchmark's host spans, over the part
of the window that is not traced."""


def read(run, span):
    d = run.spans.between(span, run.obs['t_open'], run.obs['t_host_end'])
    return 1e3 * sum(d) / len(d) if d else None
