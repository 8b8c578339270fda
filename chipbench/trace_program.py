#!/usr/bin/env python3
"""Trace a cell's rounds with the program's own spans on the device clock.

    python3 chipbench/trace_program.py --workload <cell> --seed <n>

Builds the cell as ``run.py`` does and drives its three set-up rounds;
then runs the workload's ``trace_rounds`` rounds under the profiler with
the program's tracer mirroring every span into it
(``repro.tracing.Tracer(profiler=True)``). The correctness reference is
not run. Needs a TPU.

The last line of standard output is one JSON object:

- ``metrics``: the per-layer metrics that read the program's spans
  (``metrics/service_idle_ms.py``, ``train_idle_ms.py``,
  ``chain_idle_ms.py``, ``host_transfer_mb.py``);
- ``covered``: for each of the benchmark's calls into the program
  (``model_snapshot``, ``deserialize``, ``local_train``,
  ``submit_cohort``, and all four together), the device-idle ms per round
  inside it and the share of that which falls inside a program span; and
  the idle ms per round in the benchmark's own frame (``round_frame``:
  inside its ``round`` span, outside every call);
- ``idle_by_span``: device-idle ms per round by the innermost program span;
- ``spans``: host ms, calls and bytes moved per round of each program span;
- ``stages``: how many of the device's op events name a chain stage
  (``dp``, ``quantize``, ``mask``, ``vg_sum``) themselves, and the device
  ms per round of each stage, the ops mapped to stages through the chain
  programs' optimised HLO.
"""
import argparse
import contextlib
import gzip
import json
import os
import re
import shutil
import sys
from collections import defaultdict

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

METRICS = ("service_idle_ms", "train_idle_ms", "chain_idle_ms",
           "host_transfer_mb")
CALLS = ("model_snapshot", "deserialize", "local_train", "submit_cohort")
STAGES = ("dp", "quantize", "mask", "vg_sum")
CHAIN_PROGRAMS = ("_cohort_interims", "_cohort_interims_churn",
                  "_wave_limb_state")


def walk(spans):
    for sp in spans:
        yield sp
        yield from walk(sp.children)


def span_totals(tracer) -> dict:
    """{span name: {host_s, calls, h2d_bytes, d2h_bytes, and every other
    numeric attribute}} summed over every span the tracer holds. The sum
    of an identifier (``task``, ``round``) means nothing; a count (bytes,
    rows, clients) sums to the window's."""
    tot = defaultdict(lambda: defaultdict(int, host_s=0.0, calls=0,
                                          h2d_bytes=0, d2h_bytes=0))
    for sp in walk(tracer.roots()):
        t = tot[sp.name]
        t["host_s"] += sp.wall_s
        t["calls"] += 1
        for k, v in sp.attrs.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                t[k] += v
    return {n: dict(t) for n, t in tot.items()}


def counts(tracer) -> dict:
    """{attribute: its sum over every span the tracer holds}: the
    program's counters over the window, by name."""
    out = defaultdict(int)
    for t in span_totals(tracer).values():
        for k, v in t.items():
            if k not in ("host_s", "calls"):
                out[k] += v
    return dict(out)


def transfer_bytes(tracer) -> int:
    c = counts(tracer)
    return c.get("h2d_bytes", 0) + c.get("d2h_bytes", 0)


def program_ctx(summary: dict, tracer, rounds: int) -> dict:
    """What the program-span metrics read: the window's rounds, the
    device-idle ns inside named program spans (``program_idle_ns``), the
    bytes counted (``transfer_bytes``) and any counter of the program's
    spans by name (``program_counts``: None where no span carries it)."""
    from chipbench import devtrace, progtrace
    lo, hi = devtrace.window(summary)
    return {"rounds": rounds,
            "program_idle_ns": lambda names: progtrace.idle_in_spans(
                summary, names, lo, hi),
            "transfer_bytes": transfer_bytes(tracer),
            "program_counts": counts(tracer).get}


def coverage(summary: dict, rounds: int) -> dict:
    """Device-idle ms per round inside each benchmark call (and all of
    them), and the share of it inside any program span."""
    from chipbench import devtrace, progtrace
    lo, hi = devtrace.window(summary)
    idle = progtrace.idle(summary, lo, hi)
    bench = [[n[len(devtrace.SPAN_PREFIX):], s, d]
             for n, s, d in summary["spans"]]
    program = devtrace.merged(summary["program_spans"], lo, hi)
    out = {}
    for key, names in [(c, (c,)) for c in CALLS] + [("all", CALLS)]:
        inside = progtrace.intersect(idle, progtrace.covered(bench, names,
                                                             lo, hi))
        ns = progtrace.length(inside)
        hit = progtrace.length(progtrace.intersect(inside, program))
        out[key] = {"idle_ms": ns / 1e6 / rounds,
                    "share": hit / ns if ns else None}
    # idle in the benchmark's own frame: inside its round, outside its calls
    calls = devtrace.merged([e for e in bench if e[0] != "round"], lo, hi)
    frame = [g for a, b in progtrace.covered(bench, ("round",), lo, hi)
             for g in progtrace.intersect(idle, [[a, b]])]
    own = progtrace.length(frame) - progtrace.length(
        progtrace.intersect(frame, calls))
    out["round_frame"] = {"idle_ms": own / 1e6 / rounds}
    return out


@contextlib.contextmanager
def recording_chain_calls():
    """While open, record the abstract arguments (shapes, dtypes,
    shardings and static options) of each chain program's last call:
    ``{program: (args, kwargs)}``."""
    import jax

    from repro.core import privacy_engine as pe

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding) \
            if isinstance(x, jax.Array) else x

    calls, saved = {}, {n: getattr(pe, n) for n in CHAIN_PROGRAMS}

    def recorder(name, fn):
        def call(*args, **kwargs):
            calls[name] = (jax.tree.map(abstract, args), kwargs)
            return fn(*args, **kwargs)
        return call

    for name, fn in saved.items():
        setattr(pe, name, recorder(name, fn))
    try:
        yield calls
    finally:
        for name, fn in saved.items():
            setattr(pe, name, fn)


def hlo_stages(hlo_text: str) -> dict:
    """{instruction name: chain stage} from an optimised HLO text, by the
    instruction's ``op_name`` metadata (``jit(<program>)/<stage>/...``)."""
    line = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*op_name="jit\('
                      r'\w+\)/(\w+)/')
    out = {}
    for ln in hlo_text.splitlines():
        m = line.match(ln)
        if m and m.group(2) in STAGES:
            out[m.group(1)] = m.group(2)
    return out


def stage_ms(summary: dict, rounds: int, calls: dict) -> dict:
    """Device ms per round of each chain stage: the op events inside each
    recorded chain program's executions, named by the stage that the
    program's optimised HLO gives the op (the program is compiled again
    from its recorded arguments, which the persistent cache serves)."""
    from chipbench import devtrace
    from repro.core import privacy_engine as pe
    lo, hi = devtrace.window(summary)
    ms = defaultdict(float)
    for name, (args, kwargs) in calls.items():
        text = getattr(pe, name).lower(*args, **kwargs).compile().as_text()
        by_op = hlo_stages(text)
        runs = devtrace.merged([e for e in summary["modules"]
                                if devtrace.program_name(e[0]) == name],
                               lo, hi)
        for op, start, dur in summary["ops"]:
            if any(a <= start < b for a, b in runs):
                m = re.match(r"%?([\w.\-]+)", op)
                ms[by_op.get(m.group(1) if m else "", "(other)")] += \
                    dur / 1e6 / rounds
    return dict(ms)


def ops_naming_a_stage(path: str) -> int:
    """How many of the device's op events name a chain stage
    (``/<stage>/``) in their name or in any of their stats."""
    from jax.profiler import ProfileData

    from chipbench import devtrace
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    n = 0
    for plane in data.planes:
        if not devtrace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                text = " ".join([e.name] + [str(v) for _, v in e.stats])
                n += any(f"/{s}/" in text for s in STAGES)
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    from chipbench import cells, devtrace, progtrace, run
    bench = run.load_benchmark()
    device = run.preflight(int(run.cell_entry(bench, args.workload)["chips"]))
    cell = cells.build(args.workload, args.seed)
    run.setup_rounds(cell)
    rounds = int(cell.workload["trace_rounds"])
    trace_dir = os.path.join(run.OUT_DIR, args.workload, "program_trace")
    with recording_chain_calls() as calls:
        summary, tracer, _, durations = run.trace_window(
            cell, float("inf"), rounds, trace_dir)
    named = ops_naming_a_stage(devtrace.find_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    lo, hi = devtrace.window(summary)
    ctx = program_ctx(summary, tracer, rounds)
    result = {
        "workload": args.workload, "seed": args.seed, "device": device,
        "rounds": rounds, "round_s": sum(durations) / rounds,
        "idle_share": 1 - devtrace.busy_ns(summary, lo, hi) / (hi - lo),
        "metrics": {m: run.load_reader(m)(ctx) for m in METRICS},
        "covered": coverage(summary, rounds),
        "idle_by_span": [[n, ns / 1e6 / rounds] for n, ns in
                         progtrace.idle_by_span(summary, lo, hi)],
        "spans": {n: {"host_ms": t["host_s"] * 1e3 / rounds,
                      "calls": t["calls"] / rounds,
                      "h2d_bytes": t["h2d_bytes"] / rounds,
                      "d2h_bytes": t["d2h_bytes"] / rounds}
                  for n, t in sorted(span_totals(tracer).items())},
        "stages": {"ops_naming_a_stage": named,
                   "device_ms": stage_ms(summary, rounds, calls)},
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
