"""peak_hbm_mb: the fullest chip's peak_bytes_in_use after the window,
in MB (1e6 bytes)."""


def read(ctx):
    return ctx.peak_bytes / 1e6
