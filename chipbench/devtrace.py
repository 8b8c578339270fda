"""From a profiler trace to the numbers the per-layer metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
four lists of ``[name, start_ns, duration_ns]`` on one clock:

- ``ops``: device operations (the TPU plane's ``XLA Ops`` line);
- ``modules``: device executions of whole compiled programs (its ``XLA
  Modules`` line), named as the program names them (``jit_ravel_rows``);
- ``spans``: the benchmark's host spans (``chipbench.<name>``,
  ``jax.profiler.TraceAnnotation``);
- ``program_spans``: the program's own spans, which it mirrors into the
  profiler as ``florida.<name>`` under ``repro.tracing.Tracer(profiler=
  True)``, without the prefix (``progtrace`` reads them).

The rest are pure functions of that summary, so a small recorded trace
checks them (``testdata/``).
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict

SPAN_PREFIX = "chipbench."
PROGRAM_PREFIX = "florida."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def load(path: str) -> dict:
    """Summarise one xplane file, plain or gzipped (the first TPU plane;
    the cells run on one chip)."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    out = {"ops": [], "modules": [], "spans": [], "program_spans": []}
    device_done = False
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) and not device_done:
            device_done = True
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key].extend([e.name, int(e.start_ns),
                                     int(e.duration_ns)] for e in line.events)
        elif not DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        out["spans"].append([e.name, int(e.start_ns),
                                             int(e.duration_ns)])
                    elif e.name.startswith(PROGRAM_PREFIX):
                        out["program_spans"].append(
                            [e.name[len(PROGRAM_PREFIX):], int(e.start_ns),
                             int(e.duration_ns)])
    for k in out:
        out[k].sort(key=lambda e: e[1])
    return out


def window(summary: dict, span: str = SPAN_PREFIX + "round") -> tuple:
    """(start_ns, end_ns) of the traced rounds: from the first round span's
    start to the last one's end."""
    rounds = [e for e in summary["spans"] if e[0] == span]
    if not rounds:
        raise ValueError(f"no {span!r} span in the trace")
    return rounds[0][1], max(s + d for _, s, d in rounds)


def _clip(events, lo, hi):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def merged(events, lo, hi) -> list:
    """Union of the events' intervals inside [lo, hi), as sorted disjoint
    (start, end) pairs."""
    out = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(summary: dict, lo: int, hi: int) -> int:
    return sum(b - a for a, b in merged(summary["ops"], lo, hi))


def program_name(module_event_name: str) -> str:
    """``jit__cohort_interims(123)`` / ``jit_run.4`` -> ``_cohort_interims``
    / ``run``."""
    name = re.sub(r"\(\d+\)$", "", module_event_name)
    name = re.sub(r"\.\d+$", "", name)
    return name[4:] if name.startswith("jit_") else name


def program_ns(summary: dict, programs, lo: int, hi: int) -> int:
    """Device time of the named programs (a set of ``program_name``s)
    inside the window; overlapping executions count once."""
    picked = [e for e in summary["modules"] if program_name(e[0]) in programs]
    return sum(b - a for a, b in merged(picked, lo, hi))


def op_totals(summary: dict, lo: int, hi: int, top: int = 10) -> list:
    tot = defaultdict(int)
    for name, a, b in _clip(summary["ops"], lo, hi):
        tot[name] += b - a
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]


def idle_gaps(summary: dict, lo: int, hi: int, top: int = 10) -> list:
    """Idle device time inside the window, summed by the innermost
    benchmark span the host was in at each gap's midpoint ("(none)" where
    no span covered it), largest first."""
    busy = merged(summary["ops"], lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = [(n[len(SPAN_PREFIX):], s, s + d) for n, s, d in summary["spans"]]
    tot = defaultdict(int)
    for a, b in gaps:
        mid = (a + b) // 2
        inner = [sp for sp in spans if sp[1] <= mid < sp[2]]
        name = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner \
            else "(none)"
        tot[name] += b - a
    return sorted(tot.items(), key=lambda kv: -kv[1])[:top]
