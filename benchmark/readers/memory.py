"""Peak device memory of the fullest chip after the window, in GiB: the
allocator's ``peak_bytes_in_use`` plus the program scratch it reserved
(``peak_bytes_reserved``), as ``run.py`` reports it in ``device``.  The
backend that keeps no such marks reads nothing."""


def read(ctx, spec):
    peak = ctx.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
