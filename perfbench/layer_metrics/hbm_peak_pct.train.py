"""The process's peak device memory after the window (``run.memory_peak``:
``peak_bytes_in_use`` + ``peak_bytes_reserved``, where this runtime counts a
running program's temporaries) over ``bytes_limit``, on the fullest device."""


def read(run):
    if not run["bytes_limit"]:
        return None
    return 100.0 * run["peak_bytes"] / run["bytes_limit"]
