#!/usr/bin/env python3
"""Record the small device trace, with the program's spans in it, that the
program-span tests read.

    python3 chipbench/record_program_testdata.py [--out chipbench/testdata]

Needs a TPU. Under the profiler, with ``repro.tracing.Tracer(profiler=
True)`` installed, it runs two rounds; each is a benchmark span
``chipbench.round`` holding:

- ``chipbench.local_train`` > program span ``local_train``: the program
  ``td_matmuls``, waited for, then a host sleep;
- ``chipbench.model_snapshot`` > ``snapshot``: the result pulled to the
  host (counted as ``d2h_bytes``), then a host sleep;
- ``chipbench.submit_cohort`` > ``secure_agg``: the program ``td_reduce``,
  waited for, then a host sleep;
- a host sleep outside every program span.

It writes the trace gzipped as ``program_v5e.xplane.pb.gz`` and, beside
it, ``program_v5e.json``: the sleeps, the bytes the tracer counted and the
rounds, which the tests hold the readers to.
"""
import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SLEEP_S = {"local_train": 0.02, "snapshot": 0.03, "secure_agg": 0.01,
           "outside": 0.015}
ROUNDS = 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(HERE, "testdata"))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import run, trace_program
    from repro import tracing
    device = run.preflight(1)

    def td_matmuls(x):
        for _ in range(8):
            x = jnp.tanh(x @ x)
        return x

    def td_reduce(x):
        return jnp.sum(jnp.sin(x), axis=0)

    matmuls, reduce_ = jax.jit(td_matmuls), jax.jit(td_reduce)
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048), jnp.float32)
    jax.block_until_ready(reduce_(matmuls(x)))          # compile outside

    tmp = os.path.join(ROOT, ".chipbench_out", "record_program_testdata")
    shutil.rmtree(tmp, ignore_errors=True)
    bench = lambda n: jax.profiler.TraceAnnotation(f"chipbench.{n}")
    tracer = tracing.Tracer(profiler=True)
    jax.profiler.start_trace(tmp)
    with tracing.use_tracer(tracer):
        for _ in range(ROUNDS):
            with bench("round"):
                with bench("local_train"), tracing.span("local_train"):
                    y = jax.block_until_ready(matmuls(x))
                    time.sleep(SLEEP_S["local_train"])
                with bench("model_snapshot"), \
                        tracing.span("snapshot") as sp:
                    sp.transfer(d2h=y)
                    np.asarray(y)
                    time.sleep(SLEEP_S["snapshot"])
                with bench("submit_cohort"), tracing.span("secure_agg"):
                    jax.block_until_ready(reduce_(y))
                    time.sleep(SLEEP_S["secure_agg"])
                time.sleep(SLEEP_S["outside"])
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(args.out, exist_ok=True)
    with open(path, "rb") as src, \
            gzip.open(os.path.join(args.out, "program_v5e.xplane.pb.gz"),
                      "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(args.out, "program_v5e.json"), "w") as f:
        json.dump({"device": device, "rounds": ROUNDS, "sleep_s": SLEEP_S,
                   "transfer_bytes": trace_program.transfer_bytes(tracer)},
                  f, indent=1)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"ok": True, "bytes": os.path.getsize(
        os.path.join(args.out, "program_v5e.xplane.pb.gz"))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
