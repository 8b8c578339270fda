#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (``setup_s``, from process start to the first timed round) makes
the cell's client data and weights from the seed, builds the task and the
cohort engine, and drives the first three rounds through the window's own
call; those rounds compile every program the window uses and are what the
reference is compared with. The window then runs rounds, closed loop, for
``--seconds`` (``--trace 1``: the workload's ``trace_rounds`` rounds under
the profiler). After the window the program's state is freed and the
reference replays the three rounds.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit.
The same numbers are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
OUT_DIR = os.path.join(ROOT, ".chipbench_out")

SETUP_ROUNDS = 3


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_entry(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")


def metrics_for(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def preflight(n_chips: int) -> dict:
    """The device as JAX reports it; exits non-zero without a TPU or with
    fewer than ``n_chips`` of them. Then points JAX's persistent compile
    cache at its fixed directory in the checkout (the program's
    ``compile_cache.configure`` takes the one it is given) and caches
    every program."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{devices[0].platform!r}); refusing to run")
    if len(devices) < n_chips:
        raise SystemExit(f"chipbench: the cell needs {n_chips} TPU chips, "
                         f"JAX found {len(devices)}")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro import compile_cache
    compile_cache.configure()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": n_chips}


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_programs(layer: str) -> set:
    d = os.path.join(HERE, "programs", layer)
    return {f[:-4] for f in os.listdir(d) if f.endswith(".txt")}


def _host(tree):
    import jax
    import numpy as np
    return jax.tree.map(lambda a: np.array(a, copy=True), tree)


class CompileEvents:
    """JAX's own monitoring events, counted while ``on``: traces
    (``jaxpr_trace_duration``), requests for an executable
    (``compile_requests_use_cache``: a compile or a load from the
    persistent cache) and backend compiles. The program's
    ``jit_cache_total`` sees only the jits it registers."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.on = False
        self.counts = {"traces": 0, "requests": 0, "compiles": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if self.on and event == self.REQUEST:
            self.counts["requests"] += 1

    def _duration(self, event, _secs, **_):
        if self.on and event in (self.TRACE, self.COMPILE):
            self.counts["traces" if event == self.TRACE else "compiles"] += 1


def run_window(cell, seconds: float, max_rounds: int | None = None):
    """Closed loop: rounds back to back until ``seconds`` have passed (or
    ``max_rounds`` ran). -> (window seconds, per-round seconds)."""
    durations = []
    start = time.perf_counter()
    while True:
        t = time.perf_counter()
        with cell.spans("round"):
            cell.run_round()
        durations.append(time.perf_counter() - t)
        if time.perf_counter() - start >= seconds \
                or (max_rounds and len(durations) >= max_rounds):
            break
    return time.perf_counter() - start, durations


def end_to_end(bench, name, setup_s, window_s, durations) -> dict:
    known = {
        "setup_s": lambda: setup_s,
        "round_s": lambda: window_s / len(durations),
        "round_p95_s": lambda: statistics.quantiles(durations, n=100)[94]
        if len(durations) > 1 else durations[0],
    }
    out = {}
    for m in metrics_for(bench["end_to_end"], name):
        if m["name"] not in known:
            raise SystemExit(f"chipbench: no way to take {m['name']!r}")
        out[m["name"]] = {"value": float(known[m["name"]]()),
                          "unit": m["unit"]}
    return out


def per_layer(bench, name, cell, summary, rounds, memory, peaks) -> tuple:
    from chipbench import devtrace, flops
    task = cell.workload["task"]
    submitting = int(task["clients_per_round"]) \
        - int(task.get("drop_per_round", 0))
    lo, hi = devtrace.window(summary)
    window_s = (hi - lo) / 1e9
    busy_s = devtrace.busy_ns(summary, lo, hi) / 1e9
    ctx = {
        "rounds": rounds, "window_s": window_s, "busy_s": busy_s,
        "spans_s": dict(cell.spans.total_s), "memory": memory,
        "peaks": peaks,
        "program_ns": lambda layer: devtrace.program_ns(
            summary, layer_programs(layer), lo, hi),
        "round_model_flops": flops.round_model_flops(cell.config,
                                                     cell.workload),
        "chain_bytes": flops.chain_bytes(submitting,
                                         int(cell.meta["params"])),
    }
    out = {}
    for m in metrics_for(bench["per_layer"], name):
        v = load_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = {
        "device_ops": [[n, ns / 1e9] for n, ns in
                       devtrace.op_totals(summary, lo, hi)],
        "idle_gaps": [[n, ns / 1e9] for n, ns in
                      devtrace.idle_gaps(summary, lo, hi)],
    }
    return out, {"busy_s": busy_s, "window_s": window_s}, breakdown


def setup_rounds(cell) -> tuple:
    """Drive the cell's first ``SETUP_ROUNDS`` rounds through the window's
    own call. -> (per-client losses of each round, trainable tree after
    each round (host copies), (cohort, survivors) of each round)."""
    losses, states, cohorts = [], [], []
    for _ in range(SETUP_ROUNDS):
        _, cohort, survivors, round_losses = cell.run_round()
        cohorts.append((cohort, survivors))
        losses.append(round_losses)
        states.append(_host(cell.model_now()))
    return losses, states, cohorts


def release(cell) -> dict:
    """What the reference needs of a cell, none of it made by the program;
    the cell itself (service, engine, compiled programs) is freed."""
    need = {"kind": cell.kind, "state0": cell.state0, "traffic": cell.traffic,
            "recipe": cell.recipe(), "model": cell.config["model"],
            "frozen": cell.frozen}
    cell.close()
    gc.collect()
    return need


def reference_rounds(need: dict, cohorts: list, **kw) -> tuple:
    """The reference's three rounds (``reference.run_rounds``)."""
    from chipbench import reference
    return reference.run_rounds(need["kind"], need["state0"], need["traffic"],
                                cohorts, need["recipe"], need["model"],
                                frozen=need["frozen"], **kw)


def run_cell(name: str, seed: int, seconds: float, trace: bool, bench: dict,
             device: dict, *, config=None, workload=None, t0=None) -> dict:
    """Set-up, window, reference and comparison of one cell; returns the
    result line. Tests call it on the CPU at toy widths (``config``)."""
    import jax

    from chipbench import cells, compare, flops
    from repro import tracing

    t0 = T0 if t0 is None else t0
    cell = cells.build(name, seed, config=config, workload=workload)
    prog_losses, prog_states, cohorts = setup_rounds(cell)
    setup_s = time.perf_counter() - t0
    cell.spans.reset()
    compiles = tracing.jit_cache_total()
    events = CompileEvents()
    events.on = True

    summary = None
    if trace:
        from chipbench import devtrace
        trace_dir = os.path.join(OUT_DIR, name, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            window_s, durations = run_window(
                cell, seconds, int(cell.workload["trace_rounds"]))
        finally:
            jax.profiler.stop_trace()
        summary = devtrace.load(devtrace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        window_s, durations = run_window(cell, seconds)
    events.on = False
    compiles = tracing.jit_cache_total() - compiles
    memory = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        memory.get("peak_bytes_in_use", 0)))

    if trace:
        metrics, timing, breakdown = per_layer(
            bench, name, cell, summary, len(durations), memory,
            flops.peaks(device["kind"]) if device["platform"] == "tpu"
            else None)
        device.update(timing)
    else:
        metrics = end_to_end(bench, name, setup_s, window_s, durations)

    limits = cell.workload["limits"]
    need = release(cell)
    del cell
    t_ref = time.perf_counter()
    ref_losses, ref_states, grad_norms = reference_rounds(need, cohorts)
    ref_s = time.perf_counter() - t_ref
    values = compare.numbers(need["state0"], prog_losses, prog_states,
                             ref_losses, ref_states, grad_norms)
    correct, checks = compare.judge(values, limits)
    print(f"chipbench: {len(durations)} rounds in {window_s!r} s, "
          f"{compiles} compiles in the window, reference {ref_s!r} s",
          file=sys.stderr)
    print("chipbench: in the window, JAX traced {traces}, asked for "
          "{requests} executables and compiled {compiles}".format(
              **events.counts), file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = {"correct": bool(correct), "attempted": len(durations),
              "failed": 0, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    entry = cell_entry(bench, args.workload)
    device = preflight(int(entry["chips"]))
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), bench, device)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
