"""The program's own spans on the device trace's clock.

Under ``repro.tracing.Tracer(profiler=True)`` the program mirrors each of
its spans (``begin_round``, ``snapshot``, ``local_train``, ``secure_agg``,
...) into the profiler as a host event named ``florida.<span>``.
``devtrace.load`` keeps them under ``program_spans``, as ``[name,
start_ns, duration_ns]`` without the prefix; ``idle_in_spans`` reads how
long the device sat idle while the host was inside them.

A trace of a program that mirrors nothing has no such events: every
reading is then 0 and the metrics that read them report nothing.
"""
from __future__ import annotations

import bisect
from collections import defaultdict

from chipbench import devtrace


def idle(summary: dict, lo: int, hi: int) -> list:
    """The device's idle intervals inside [lo, hi): the gaps between the
    union of its operations, as sorted disjoint [start, end) pairs."""
    out, t = [], lo
    for a, b in devtrace.merged(summary["ops"], lo, hi):
        if a > t:
            out.append([t, a])
        t = max(t, b)
    if hi > t:
        out.append([t, hi])
    return out


def intersect(xs: list, ys: list) -> list:
    """Intersection of two sorted disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append([a, b])
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def length(intervals: list) -> int:
    return sum(b - a for a, b in intervals)


def covered(events: list, names, lo: int, hi: int) -> list:
    """Union of the named events' intervals inside [lo, hi)."""
    names = set(names)
    return devtrace.merged([e for e in events if e[0] in names], lo, hi)


def idle_in_spans(summary: dict, names, lo: int, hi: int) -> int:
    """Device-idle ns inside [lo, hi) during which the host was inside a
    program span of one of ``names``: each idle gap is cut at the spans'
    edges, and time under nested or overlapping spans counts once."""
    return length(intersect(idle(summary, lo, hi),
                            covered(summary["program_spans"], names, lo,
                                    hi)))


def idle_by_span(summary: dict, lo: int, hi: int) -> list:
    """Device-idle ns inside [lo, hi) by the innermost program span the
    host was in (``(none)`` outside every one), cut at span edges; largest
    first."""
    spans = [(n, s, s + d) for n, s, d in summary["program_spans"]]
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    tot = defaultdict(int)
    for a, b in idle(summary, lo, hi):
        cuts = [a] + edges[bisect.bisect_right(edges, a):
                           bisect.bisect_left(edges, b)] + [b]
        for x, y in zip(cuts, cuts[1:]):
            inner = [sp for sp in spans if sp[1] <= x and y <= sp[2]]
            name = min(inner, key=lambda sp: sp[2] - sp[1])[0] if inner \
                else "(none)"
            tot[name] += y - x
    return sorted(tot.items(), key=lambda kv: -kv[1])
