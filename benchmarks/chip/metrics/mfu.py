"""The whole job's share of the chips' bf16 peak, in percent.

The operations are the algorithm's: the configuration's model file counts
the sampler transitions of a job over the real rows (``job_flops``). They
are divided by the traced window's length, the chips and the published
peak of one chip.
"""

from chipbench import trace


def read(ctx):
    span = trace.busy_seconds(ctx["trace"])
    if span is None or ctx["peak"] is None:
        return None
    _, window_s = span
    cell = ctx["cell"]
    flops = ctx["jobs"] * cell.model.job_flops(cell.config)
    return 100.0 * flops / (window_s * cell.chips * ctx["peak"]["bf16_flops"])
