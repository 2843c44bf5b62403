"""How late the load generator sent its requests, 99th percentile, in
milliseconds: due time against the actual send."""


def read(run):
    late = run.obs.get('late_p99_s')
    return None if late is None else 1e3 * late
