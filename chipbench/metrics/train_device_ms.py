"""Device time per round of the cohort engine's programs (the programs
listed under ``programs/train/``), from the profiler trace."""


def read(ctx):
    ns = ctx["program_ns"]("train")
    return ns / 1e6 / ctx["rounds"] if ns and ctx["rounds"] else None
