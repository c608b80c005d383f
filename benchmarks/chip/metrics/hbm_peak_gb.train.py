"""Peak device memory in use (the fullest chip's peak_bytes_in_use after
the traced iterations), in GB."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 1e9 if b else None
