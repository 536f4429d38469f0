"""Mean seconds of the ``sample`` span per job (the sampling layer)."""

from chipbench import trace


def read(ctx):
    spans = trace.span_seconds(ctx["trace"], "sample")
    return sum(spans) / len(spans) if spans else None
