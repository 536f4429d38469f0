"""Percent of the traced window in which no operation ran, averaged over
the chips."""

from chipbench import trace


def read(ctx):
    share = trace.idle_share(ctx["trace"])
    return None if share is None else 100.0 * share
