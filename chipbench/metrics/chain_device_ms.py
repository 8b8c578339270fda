"""Device time per round of the privacy chain's programs (ravel, DP,
quantize, masks, virtual-group sums, limb combine and finalize: the
programs listed under ``programs/chain/``), from the profiler trace."""


def read(ctx):
    ns = ctx["program_ns"]("chain")
    return ns / 1e6 / ctx["rounds"] if ns and ctx["rounds"] else None
