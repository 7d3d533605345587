"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read
when the window has closed and before the reference runs."""


def read(run, spec):
    if run.mem_peak_bytes is None:
        return None
    return run.mem_peak_bytes / 1e9
