"""``memory_stats()["peak_bytes_in_use"]`` of the fullest device after the
window, before the reference runs."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
