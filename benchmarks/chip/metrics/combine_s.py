"""Mean seconds of the ``combine`` span per job (the combine layer)."""

from chipbench import trace


def read(ctx):
    spans = trace.span_seconds(ctx["trace"], "combine")
    return sum(spans) / len(spans) if spans else None
