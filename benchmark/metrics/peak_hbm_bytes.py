"""memory_stats()["peak_bytes_in_use"] on the fullest device after the
window: what set-up left resident plus the window's scratch."""


def read(ctx):
    peak = max((m["peak_bytes_in_use"] for m in ctx.after["memory"]),
               default=0)
    return float(peak) if peak else None
