"""Percent of the ``combine`` spans in which no operation ran on the chips."""

from chipbench import trace


def read(ctx):
    share = trace.idle_share(ctx["trace"], "combine")
    return None if share is None else 100.0 * share
