"""Device-busy microseconds inside the ``combine`` spans per IMG site
update the program counted (``combine.img.chain``'s ``img_sites``: sweeps
x chains x M)."""

from chipbench import program


def read(ctx):
    return program.per_unit_us(ctx, "combine", "img_sites", "combine.stage")
