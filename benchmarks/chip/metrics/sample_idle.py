"""Percent of the ``sample`` spans in which no operation ran on the chips."""

from chipbench import trace


def read(ctx):
    share = trace.idle_share(ctx["trace"], "sample")
    return None if share is None else 100.0 * share
