"""Host seconds per job in which JAX traced, lowered or obtained an
executable inside the program's stage spans (the host layer).

The union of JAX's trace and lowering events (``program.COMPILE_EVENTS``)
inside the ``sample.stage`` and ``combine.stage`` spans of the trace, plus
the seconds the program's listener timed around each executable obtained,
compiled or read from the persistent cache. JAX obtains an executable after
it lowers the program, outside those events, so the two do not overlap.
"""

from chipbench import program, trace


def read(ctx):
    obtained = program.counter(ctx, "backend_compile_s")
    tr = ctx["trace"]
    stages = trace.union((s, e) for n, s, e in tr.host if n in program.STAGES)
    if obtained is None or not stages:
        return None
    events = trace.union((s, e) for n, s, e in tr.host if n in program.COMPILE_EVENTS)
    host_ns = sum(trace.covered(events, s, e) for s, e in stages)
    return (host_ns * 1e-9 + obtained) / ctx["jobs"]
