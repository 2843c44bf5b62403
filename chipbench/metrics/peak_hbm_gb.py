"""Peak device memory on the fullest chip, in GB, the same sum in every
cell: ``memory_stats()['peak_bytes_in_use']`` (resident arrays) plus the
scratch that the largest program of the window declares
(``memory_analysis().temp_size_in_bytes``), which that counter does not
see.  The run's MEMORY line gives the two parts."""


def read(run):
    if run.rehearse:
        return None
    return run.memory_peak_bytes() / 1e9
