#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--faults 3] [--out chiprun_out/calib-<cell>.jsonl]

For each seed, in a process of its own: the program's first three rounds
(the same set-up a benchmark run makes, through the window's own call)
against the float32 reference, which gives the lower readings. For the
first ``--faults`` seeds also, each against the same float32 reference:

- ``control``: the reference put in the program's place, computed in
  bfloat16, one precision below the configuration's float32;
- ``half_batch``: the reference with half of each client's batch left out,
  the mean taken over the rest;
- ``unchanged``: a server step that returns its state unchanged (the
  program's losses, the model never moving).

The first seed also reads the device's memory counters after its set-up
rounds, beside the compiler's account of the cohort training call.

Each seed prints one JSON line (and appends it to ``--out``), with each
round's widest, median and mean loss gap and every client's loss (of the
program, each fault and the reference) beside the numbers compared
(``by_round``). No window runs and nothing is timed.
"""
import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def memory_account(cell, cohort) -> dict:
    """The device's memory counters after the set-up rounds, beside the
    compiler's account of the cohort training call (one wave) as run."""
    import jax

    from repro.core.cohort_engine import stack_trees
    stats = jax.devices()[0].memory_stats() or {}
    engine = cell.engine
    width = engine.wave_size or len(cohort)
    batches = stack_trees([engine.batch_fn(c, 0) for c in cohort[:width]])
    mem = engine._cohort_fn(False).lower(
        cell.template, batches, engine.spec.frozen).compile() \
        .memory_analysis()
    return {"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_in_use": stats.get("bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "train_call": {"argument": mem.argument_size_in_bytes,
                           "output": mem.output_size_in_bytes,
                           "temp": mem.temp_size_in_bytes,
                           "alias": mem.alias_size_in_bytes}}


def readings(name: str, seed: int, faults: bool, *, memory: bool = False,
             config=None, workload=None) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from chipbench import cells, compare, run

    cell = cells.build(name, seed, config=config, workload=workload)
    prog_losses, prog_states, cohorts = run.setup_rounds(cell)
    account = memory_account(cell, cohorts[-1][1]) if memory else None
    need = run.release(cell)
    del cell
    ref_losses, ref_states, grads = run.reference_rounds(need, cohorts)

    by_round = {}

    def against_ref(variant, losses, states):
        # each round's widest and median client loss gap, the gap of the
        # cohort's mean loss, and every client's loss: what the limits are
        # chosen from, not compared themselves
        by_round[variant] = {
            "widest_loss_gap": [compare.loss_gap([p], [r])
                                for p, r in zip(losses, ref_losses)],
            "median_loss_gap": [compare.median_loss_gap(p, r)
                                for p, r in zip(losses, ref_losses)],
            "mean_loss_gap": [abs(float(np.mean(p)) / float(np.mean(r)) - 1)
                              for p, r in zip(losses, ref_losses)],
            "losses": [np.asarray(p, np.float64).tolist() for p in losses]}
        return compare.numbers(need["state0"], losses, states, ref_losses,
                               ref_states, grads)

    out = {"seed": seed,
           "program": against_ref("program", prog_losses, prog_states)}
    if account:
        out["memory"] = account
    if faults:
        ctl_losses, ctl_states, _ = run.reference_rounds(
            need, cohorts, dtype=jnp.bfloat16)
        out["control"] = against_ref("control", ctl_losses, ctl_states)
        hb_losses, hb_states, _ = run.reference_rounds(need, cohorts,
                                                       half_batch=True)
        out["half_batch"] = against_ref("half_batch", hb_losses, hb_states)
        out["unchanged"] = against_ref("unchanged", prog_losses,
                                       [need["state0"]] * len(prog_states))
    by_round["reference"] = {"losses": [np.asarray(r, np.float64).tolist()
                                        for r in ref_losses]}
    out["by_round"] = by_round
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--faults", type=int, default=3,
                    help="run the control and the faults on this many of "
                         "the seeds (the first ones)")
    ap.add_argument("--memory", type=int, default=1,
                    help="read the memory account on the first seed")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    if len(seeds) > 1:
        # One process per seed, one after the other: the program's jit
        # registry keeps every engine (and a LoRA base) for the life of
        # its process, and a chip serves one process at a time.
        failed = 0
        for i, seed in enumerate(seeds):
            cmd = [sys.executable, os.path.abspath(__file__),
                   "--workload", args.workload, "--seeds", str(seed),
                   "--faults", str(int(i < args.faults)),
                   "--memory", str(int(i == 0 and args.memory))]
            if args.out:
                cmd += ["--out", args.out]
            rc = subprocess.run(cmd).returncode
            if rc:
                print(f"calibrate: seed {seed} exited {rc}", file=sys.stderr)
                failed += 1
        return 1 if failed else 0
    from chipbench import run
    entry = run.cell_entry(run.load_benchmark(), args.workload)
    run.preflight(int(entry["chips"]))
    line = json.dumps(readings(args.workload, seeds[0], args.faults > 0,
                               memory=bool(args.memory)))
    print(line, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
