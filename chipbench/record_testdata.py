#!/usr/bin/env python3
"""Record the small device trace that the reducer's tests read.

    python3 chipbench/record_testdata.py [--out chipbench/testdata]

Needs a TPU. Under the profiler it runs two rounds, each a benchmark span
``chipbench.round`` holding three spans: ``matmuls`` (the program
``td_matmuls``, waited for), ``host_sleep`` (the host sleeps 30 ms while
the device has nothing queued) and ``reduce`` (the program ``td_reduce``,
waited for). It writes the trace gzipped as ``trace_v5e.xplane.pb.gz``
and, beside it, ``trace_v5e.json``: what the recording knows of itself
(the programs, the spans, the sleep), which the tests hold the reducer to.
"""
import argparse
import gzip
import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

SLEEP_S = 0.03


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join(HERE, "testdata"))
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from chipbench import run
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

    tmp = os.path.join(ROOT, ".chipbench_out", "record_testdata")
    shutil.rmtree(tmp, ignore_errors=True)
    span = lambda n: jax.profiler.TraceAnnotation(f"chipbench.{n}")
    jax.profiler.start_trace(tmp)
    for _ in range(2):
        with span("round"):
            with span("matmuls"):
                y = jax.block_until_ready(matmuls(x))
            with span("host_sleep"):
                time.sleep(SLEEP_S)
            with span("reduce"):
                jax.block_until_ready(reduce_(y))
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(args.out, exist_ok=True)
    with open(path, "rb") as src, \
            gzip.open(os.path.join(args.out, "trace_v5e.xplane.pb.gz"),
                      "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(args.out, "trace_v5e.json"), "w") as f:
        json.dump({"device": device, "rounds": 2,
                   "programs": ["td_matmuls", "td_reduce"],
                   "spans": ["round", "matmuls", "host_sleep", "reduce"],
                   "sleep_s": SLEEP_S}, f, indent=1)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"ok": True, "bytes": os.path.getsize(
        os.path.join(args.out, "trace_v5e.xplane.pb.gz"))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
