"""Device-idle ms per round while the host was inside the service's
program spans: ``begin_round``, ``snapshot``, ``deserialize``,
``server_update``, ``finish_round`` and ``report_dropout`` (the profiler
trace, with the program's spans mirrored into it)."""

SPANS = ("begin_round", "snapshot", "deserialize", "server_update",
         "finish_round", "report_dropout")


def read(ctx):
    idle_ns = ctx.get("program_idle_ns")
    if idle_ns is None or not ctx["rounds"]:
        return None
    return idle_ns(SPANS) / 1e6 / ctx["rounds"]
