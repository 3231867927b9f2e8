"""Peak device memory on the fullest chip after the window
(``memory_stats()["peak_bytes_in_use"]``), before the reference runs."""


def read(ctx):
    if not ctx["memory_peak_bytes"]:
        return None
    return ctx["memory_peak_bytes"] / 2.0 ** 30
