"""Device-idle ms per round while the host was inside the cohort engine's
``local_train`` span (batches made on the host, waves pulled back), from
the profiler trace with the program's spans mirrored into it."""

SPANS = ("local_train",)


def read(ctx):
    idle_ns = ctx.get("program_idle_ns")
    if idle_ns is None or not ctx["rounds"]:
        return None
    return idle_ns(SPANS) / 1e6 / ctx["rounds"]
