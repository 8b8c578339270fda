"""Device-idle ms per round while the host was inside the privacy chain's
spans: ``vg_plan`` (virtual groups, buckets, row order) and
``secure_agg`` (ravel, survivor scatter, the fused chain, mask recovery,
limb combine), from the profiler trace with the program's spans mirrored
into it."""

SPANS = ("vg_plan", "secure_agg")


def read(ctx):
    idle_ns = ctx.get("program_idle_ns")
    if idle_ns is None or not ctx["rounds"]:
        return None
    return idle_ns(SPANS) / 1e6 / ctx["rounds"]
