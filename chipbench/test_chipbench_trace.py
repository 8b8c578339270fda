"""The reduction from a profiler trace to the per-layer numbers: busy
union, program time and gap attribution, checked on hand-made summaries
and on a small trace recorded on a TPU v5e (``testdata/``, written by
``record_testdata.py``: two rounds, each two programs with a 30 ms host
sleep between them)."""
import json
import os

import pytest

from chipbench import devtrace

TESTDATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "testdata")

MS = 1_000_000


def summary():
    """Two rounds of 100 ms: ops from 0-30 and 20-40 (overlapping), a
    program over 50-70; the host sleeps in ``wait`` over 40-50 and again
    over 150-190, in ``submit`` over 70-100."""
    return {
        "ops": [["fusion.1", 0, 30 * MS], ["fusion.2", 20 * MS, 20 * MS],
                ["fusion.1", 50 * MS, 20 * MS],
                ["fusion.3", 100 * MS, 50 * MS]],
        "modules": [["jit_run(7)", 0, 40 * MS],
                    ["jit_ravel_rows(3)", 50 * MS, 20 * MS],
                    ["jit_run(7)", 100 * MS, 50 * MS]],
        "spans": [["chipbench.round", 0, 100 * MS],
                  ["chipbench.wait", 40 * MS, 10 * MS],
                  ["chipbench.submit", 70 * MS, 30 * MS],
                  ["chipbench.round", 100 * MS, 100 * MS],
                  ["chipbench.wait", 150 * MS, 40 * MS]],
    }


def test_window_spans_the_rounds():
    assert devtrace.window(summary()) == (0, 200 * MS)
    with pytest.raises(ValueError, match="round"):
        devtrace.window({"spans": []})


def test_busy_is_the_union_of_op_intervals():
    s = summary()
    assert devtrace.merged(s["ops"], 0, 200 * MS) == [
        [0, 40 * MS], [50 * MS, 70 * MS], [100 * MS, 150 * MS]]
    assert devtrace.busy_ns(s, 0, 200 * MS) == 110 * MS
    # clipped to a window
    assert devtrace.busy_ns(s, 25 * MS, 120 * MS) == 15 * MS + 20 * MS \
        + 20 * MS


def test_program_time_by_name():
    s = summary()
    assert devtrace.program_name("jit_run(7)") == "run"
    assert devtrace.program_name("jit__cohort_interims.12") == \
        "_cohort_interims"
    assert devtrace.program_ns(s, {"run"}, 0, 200 * MS) == 90 * MS
    assert devtrace.program_ns(s, {"ravel_rows"}, 0, 200 * MS) == 20 * MS
    assert devtrace.program_ns(s, {"absent"}, 0, 200 * MS) == 0


def test_idle_gaps_named_by_the_innermost_span():
    # gaps 40-50 and 150-200 fall to ``wait`` (by each gap's midpoint),
    # 70-100 to ``submit``
    gaps = devtrace.idle_gaps(summary(), 0, 200 * MS)
    assert gaps == [("wait", 60 * MS), ("submit", 30 * MS)]


def test_op_totals_largest_first():
    tot = devtrace.op_totals(summary(), 0, 200 * MS)
    assert dict(tot) == {"fusion.1": 50 * MS, "fusion.3": 50 * MS,
                         "fusion.2": 20 * MS}
    assert tot[-1] == ("fusion.2", 20 * MS)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(TESTDATA, "trace_v5e.json")) as f:
        meta = json.load(f)
    return meta, devtrace.load(os.path.join(TESTDATA,
                                            "trace_v5e.xplane.pb.gz"))


def test_recorded_trace_has_spans_programs_and_ops(recorded):
    meta, s = recorded
    names = [n[len(devtrace.SPAN_PREFIX):] for n, _, _ in s["spans"]]
    assert sorted(names) == sorted(meta["spans"] * meta["rounds"])
    assert sorted(devtrace.program_name(n) for n, _, _ in s["modules"]) \
        == sorted(meta["programs"] * meta["rounds"])
    assert s["ops"] and all(d >= 0 for _, _, d in s["ops"])


def test_recorded_trace_busy_is_the_programs_time(recorded):
    meta, s = recorded
    lo, hi = devtrace.window(s)
    busy = devtrace.busy_ns(s, lo, hi)
    programs = devtrace.program_ns(s, set(meta["programs"]), lo, hi)
    assert 0 < busy <= programs <= hi - lo
    # the device ops of a program lie inside its execution
    assert busy > 0.9 * programs


def test_recorded_trace_idle_time_falls_to_the_host_sleep(recorded):
    meta, s = recorded
    lo, hi = devtrace.window(s)
    gaps = dict(devtrace.idle_gaps(s, lo, hi))
    assert max(gaps, key=gaps.get) == "host_sleep"
    sleep_ns = meta["sleep_s"] * 1e9 * meta["rounds"]
    assert sleep_ns <= gaps["host_sleep"] < 1.5 * sleep_ns
