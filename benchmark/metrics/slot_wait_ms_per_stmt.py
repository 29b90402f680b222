"""Admission + slots: self time of `sched.slot` (the wait for a device
dispatch slot) per statement completed."""

from benchlib import spans


def read(ctx):
    return spans.ms_per_stmt(ctx, "sched.slot")
