"""Needed bytes over peak HBM bandwidth over device busy time (one chip);
bound: bytes."""

from benchlib import rates


def read(ctx):
    return rates.hbm_roofline_pct(ctx)
