"""Executables JAX obtained per job, compiled or read from the persistent
cache, inside the program's stage spans (the host layer)."""

from chipbench import program


def read(ctx):
    n = program.counter(ctx, "executables")
    return None if n is None else n / ctx["jobs"]
