"""The most device memory in use at once on the fullest chip over the
run, as the runtime's allocator reports it (``peak_bytes_in_use``), in
GB."""


def read(ctx):
    return ctx.memory_peak_bytes / 1e9 if ctx.memory_peak_bytes else None
