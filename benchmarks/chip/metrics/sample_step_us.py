"""Device-busy microseconds inside the ``sample`` spans per sequential
chain step the program counted (``sample.stage``'s ``steps``: warmup,
burn-in and kept draws of each chain, all chains stepping together)."""

from chipbench import program


def read(ctx):
    return program.per_unit_us(ctx, "sample", "steps", "sample.stage")
