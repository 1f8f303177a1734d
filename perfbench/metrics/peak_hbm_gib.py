"""Peak device memory of the fullest chip, in GiB: the TPU runtime's
``peak_bytes_in_use`` (arrays) plus ``peak_bytes_reserved`` (the compiled
programs' temporaries), read after the window."""


def read(ctx):
    if ctx.memory_peak <= 0:
        return None
    return ctx.memory_peak / 2 ** 30
