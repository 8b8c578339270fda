"""Flight-recorder layer (ISSUE 10): span tracer semantics, Perfetto
export, flight transcripts, the jit-cache probe's recompile-regression
gates (async batch pad classes, streaming-wave width), the typed metrics
registry, MetricsStore whole-store persistence, and the traced == untraced
bit-identity contract."""
import json
import pickle
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.fl.telemetry import (FIXED_BUCKETS, MetricsRegistry,
                                MetricsStore)


def _flatten(span):
    out = [span]
    for c in span.children:
        out.extend(_flatten(c))
    return out


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_null_tracer_is_default_and_free(monkeypatch):
    assert not tracing.enabled()
    sp = tracing.span("anything", task=1)

    def counted(*a, **k):
        raise AssertionError("the null tracer counted bytes")

    monkeypatch.setattr(tracing, "tree_bytes", counted)
    with sp as inner:
        assert inner is sp
        assert inner.set(x=1) is inner
        assert inner.transfer(h2d=np.zeros(4), d2h=jnp.zeros(4)) is inner
    assert sp.attrs == {}
    # shared singleton: no allocation per span site
    assert tracing.span("other") is sp


def test_transfer_counts_host_and_device_bytes():
    host = {"a": np.zeros((3, 4), np.float32), "b": np.zeros(5, np.int8)}
    dev = {"a": jnp.zeros((2, 8), jnp.float32), "h": np.zeros(7)}
    with tracing.use_tracer(tracing.Tracer()) as tr:
        with tracing.span("move") as sp:
            # only host leaves go h2d, only device leaves come d2h
            sp.transfer(h2d=(host, dev), d2h=(host, dev))
            sp.transfer(h2d=np.zeros(2, np.float32))
    (root,) = tr.roots()
    assert root.attrs == {"h2d_bytes": 48 + 5 + 56 + 8, "d2h_bytes": 64}


def test_tracing_imports_without_jax():
    import subprocess
    import sys
    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro import tracing\n"
            "with tracing.use_tracer(tracing.Tracer()):\n"
            "    with tracing.span('s'):\n"
            "        pass\n"
            "assert tracing.jit_cache_total() == 0\n")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_profiler_mode_mirrors_spans(tmp_path):
    """``Tracer(profiler=True)``: each span is also a profiler annotation
    named ``florida.<name>``, nested as the spans are."""
    from jax.profiler import ProfileData

    tr = tracing.Tracer(profiler=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.use_tracer(tr):
            with tracing.span("outer"):
                with tracing.span("inner"):
                    jax.block_until_ready(jnp.ones(4) * 2)
    finally:
        jax.profiler.stop_trace()
    (path,) = list(tmp_path.glob("**/*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    events = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
              for p in data.planes for ln in p.lines for e in ln.events
              if e.name.startswith(tracing.PROFILER_PREFIX)}
    assert set(events) == {"florida.outer", "florida.inner"}
    (o0, o1), (i0, i1) = events["florida.outer"], events["florida.inner"]
    assert o0 <= i0 <= i1 <= o1
    assert [r.name for r in tr.roots()] == ["outer"]


def test_span_nesting_and_attrs():
    with tracing.use_tracer(tracing.Tracer()) as tr:
        with tracing.span("round", task=3) as outer:
            with tracing.span("inner_a") as a:
                a.set(n=7)
            with tracing.span("inner_b"):
                pass
        roots = tr.roots()
    assert [r.name for r in roots] == ["round"]
    assert roots[0].attrs == {"task": 3}
    assert [c.name for c in roots[0].children] == ["inner_a", "inner_b"]
    assert roots[0].children[0].attrs == {"n": 7}
    assert outer.wall_s >= 0.0 and outer.cpu_s >= 0.0
    a, b = roots[0].children
    assert outer.t0 <= a.t0 <= a.t1 <= b.t1 <= outer.t1


@pytest.mark.parametrize("program", ["_cohort_interims",
                                     "_wave_limb_state"])
def test_chain_stages_are_named_scopes_on_the_device(program):
    """The chain's four fused stages are ``jax.named_scope``s: each names
    its operations in the compiled program's op metadata, where a
    profiler trace of the device can find them."""
    import re

    from repro.core import dp as dp_mod
    from repro.core import privacy_engine as pe
    from repro.core import secure_agg as sa
    from repro.core.virtual_groups import make_virtual_groups

    cids = [f"c{i}" for i in range(8)]
    buckets = pe.plan_buckets(make_virtual_groups(cids, 4, seed=0), cids)
    seed = jnp.asarray([1, 2], jnp.uint32)
    key = jax.random.PRNGKey(0)
    cfg = dict(secure_cfg=sa.SecureAggConfig(),
               dp_cfg=dp_mod.DPConfig(mechanism="local", clip_norm=0.5,
                                      noise_multiplier=1.0))
    if program == "_cohort_interims":
        lowered = pe._cohort_interims.lower(
            jnp.zeros((8, 32)), seed, key,
            tuple(jnp.asarray(b.rows, jnp.int32) for b in buckets),
            tuple(jnp.asarray(b.vg_ids, jnp.uint32) for b in buckets),
            bucket_shapes=tuple((b.g, b.n_groups) for b in buckets),
            n_shards=1, **cfg)
    else:
        lowered = pe._wave_limb_state.lower(
            jnp.zeros((8, 32)), jnp.arange(8, dtype=jnp.uint32), seed, key,
            jnp.asarray([0, 1], jnp.uint32), jnp.ones(2, bool), g=4, **cfg)
    text = lowered.compile().as_text()
    scopes = set(re.findall(rf'op_name="jit\({program}\)/(\w+)/', text))
    assert {"dp", "quantize", "mask", "vg_sum"} <= scopes


def test_thread_safety_separate_stacks():
    tr = tracing.Tracer()
    errs = []

    def spans(i):
        try:
            with tr.span("outer", thread=i):
                with tr.span("inner", thread=i):
                    pass
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    with tracing.use_tracer(tr):
        threads = [threading.Thread(target=spans, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errs
    roots = tr.roots()
    assert len(roots) == 8
    for r in roots:
        # nesting stayed per-thread: exactly one child, same thread tag
        assert [c.name for c in r.children] == ["inner"]
        assert r.children[0].attrs["thread"] == r.attrs["thread"]


def test_max_spans_cap_counts_drops():
    tr = tracing.Tracer(max_spans=2)
    with tracing.use_tracer(tr):
        for _ in range(5):
            with tr.span("s"):
                pass
    assert len(tr.roots()) == 2 and tr.n_dropped == 3


def test_tracer_pickle_round_trip():
    tr = tracing.Tracer()
    with tracing.use_tracer(tr):
        with tr.span("kept", k=1):
            pass
    tr2 = pickle.loads(pickle.dumps(tr))
    assert [r.name for r in tr2.roots()] == ["kept"]
    # lock/tls were dropped and recreated: the copy still collects
    with tracing.use_tracer(tr2):
        with tr2.span("more"):
            pass
    assert [r.name for r in tr2.roots()] == ["kept", "more"]


def test_use_tracer_restores_previous():
    prev = tracing.get_tracer()
    with tracing.use_tracer(tracing.Tracer()):
        assert tracing.enabled()
    assert tracing.get_tracer() is prev


def test_perfetto_export_structure(tmp_path):
    tr = tracing.Tracer()
    with tracing.use_tracer(tr):
        with tr.span("round", task=1):
            with tr.span("aggregate"):
                with tr.span("dp"):
                    pass
    path = tr.export_perfetto(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert [e["name"] for e in xs] == ["round", "aggregate", "dp"]
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0 and e["pid"] == 0
        assert "cpu_ms" in e["args"]
    metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    assert doc["otherData"]["n_spans"] == 3


def test_stage_list_offsets_and_depth():
    tr = tracing.Tracer()
    with tracing.use_tracer(tr):
        with tr.span("round") as root:
            with tr.span("a"):
                with tr.span("b"):
                    pass
    rows = tracing.stage_list(root)
    assert [(r["name"], r["depth"]) for r in rows] == \
        [("round", 0), ("a", 1), ("b", 2)]
    assert rows[0]["t0_ms"] == 0.0
    assert rows[1]["t0_ms"] <= rows[2]["t0_ms"]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_round_trip(tmp_path):
    fl = tracing.FlightRecorder(str(tmp_path / "flight"))
    assert fl.read(1) == [] and fl.task_ids() == []
    tr = tracing.Tracer()
    with tracing.use_tracer(tr):
        with tr.span("round") as root:
            with tr.span("aggregate"):
                pass
    fl.record(1, tracing.round_event(
        round_idx=0, cohort=["a", "b", "c"], survivors=["a", "b"],
        n_shards=2, stage2_route="churn_recovery", span_tree=root,
        metrics={"n_selected": 3}))
    fl.record(1, tracing.round_event(
        round_idx=1, cohort=["a"], survivors=[], voided=True,
        void_reason="all_dropped"))
    events = fl.read(1)
    assert fl.task_ids() == [1]
    assert events[0]["event"] == "round"
    assert events[0]["cohort"] == ["a", "b", "c"]
    assert events[0]["survivors"] == ["a", "b"]
    assert events[0]["n_dropped"] == 1
    assert events[0]["stage2_route"] == "churn_recovery"
    assert events[0]["n_shards"] == 2
    assert events[0]["metrics"] == {"n_selected": 3}
    assert [s["name"] for s in events[0]["stages"]] == \
        ["round", "aggregate"]
    assert events[0]["wall_ms"] >= 0 and "ts_unix" in events[0]
    assert events[1]["event"] == "round_voided"
    assert events[1]["void_reason"] == "all_dropped"
    assert "stages" not in events[1]

    doc = tracing.perfetto_from_flight(events, 1)
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    # round 0's two stages plus round 1's single voided block
    assert [e["name"] for e in xs] == ["round", "aggregate",
                                      "round_voided"]


def test_round_event_null_span_has_no_stages():
    ev = tracing.round_event(round_idx=0, cohort=["a"], survivors=["a"],
                             span_tree=tracing.span("nope"))
    assert "stages" not in ev and "wall_ms" not in ev


# ---------------------------------------------------------------------------
# jit cache probe + recompile-regression gates
# ---------------------------------------------------------------------------

def test_register_jit_counts_executables():
    fn = jax.jit(lambda x: x * 2)
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jax build exposes no _cache_size")
    tracing.register_jit("test_probe.double", fn)
    try:
        base = tracing.jit_cache_sizes().get("test_probe.double", 0)
        fn(jnp.zeros(4))
        fn(jnp.zeros(8))
        assert tracing.jit_cache_sizes()["test_probe.double"] == base + 2
        fn(jnp.zeros(8))   # cache hit: no growth
        assert tracing.jit_cache_sizes()["test_probe.double"] == base + 2
    finally:
        tracing._DYNAMIC_JITS.pop(("test_probe.double", id(fn)), None)


def test_register_jit_does_not_keep_engines_alive():
    """The probe holds an engine's programs weakly: once the engine is
    gone its frozen base is freed, and the probe still counts the shared
    entry points."""
    import gc
    import weakref

    from repro.core import privacy_engine as pe
    from repro.core.cohort_engine import CohortEngine, LocalTrainSpec
    from repro.optim.adamw import adamw

    def loss(p, b, frozen):
        return jnp.mean((b["x"] @ (frozen["w"] + p["d"])) ** 2)

    frozen = {"w": jnp.ones((4, 2), jnp.float32)}
    engine = CohortEngine(
        LocalTrainSpec(loss_fn=loss, optimizer=adamw(0.1), local_steps=1,
                       frozen=frozen),
        lambda cid, r: {"x": np.ones((1, 3, 4), np.float32)})
    params = {"d": jnp.zeros((4, 2), jnp.float32)}
    jax.block_until_ready(engine.run_cohort_stacked(params, ["a", "b"], 0))
    jax.block_until_ready(pe.ravel_rows({"w": jnp.zeros((2, 3))}))
    sizes = tracing.jit_cache_sizes()
    assert sizes["cohort_engine.shared"] >= 1
    assert sizes["privacy_engine.ravel_rows"] >= 1
    gone = weakref.ref(frozen["w"])
    del engine, frozen
    gc.collect()
    assert gone() is None
    after = tracing.jit_cache_sizes()
    assert after["privacy_engine.ravel_rows"] == \
        sizes["privacy_engine.ravel_rows"]
    assert after.get("cohort_engine.shared", 0) \
        <= sizes["cohort_engine.shared"] - 1


def test_async_pad_classes_no_recompile_second_batch():
    """PR-4 fixed-shape contract, now pinned by the probe: once one
    same-shape batch (pad class) and one drain have compiled, further
    same-shape batches — including on a FRESH server — add ZERO compiled
    executables across the shared jitted entry points."""
    from repro.core.dp import DPConfig
    from repro.core.orchestrator import AsyncServer
    from repro.core.strategies import FedBuff

    def mk():
        return AsyncServer(
            {"w": jnp.zeros(16, jnp.float32)},
            FedBuff(buffer_size=4),
            DPConfig(mechanism="local", clip_norm=0.5,
                     noise_multiplier=1.0))

    rng = np.random.RandomState(0)

    def batch(server):
        rows = jnp.asarray(rng.uniform(-1, 1, (3, 16)), jnp.float32)
        server.submit_batch(rows, [1.0] * 3, [0] * 3)

    server = mk()
    batch(server)             # warm the 3-row pad class (buffer at 3)
    batch(server)             # warm the drain (fills at 4, 2 left over)
    before = tracing.jit_cache_total()
    batch(server)             # 5 -> drain -> 1: same shapes throughout
    batch(server)             # 4 -> drain -> 0
    batch(mk())               # a fresh server reuses the shared jits too
    assert tracing.jit_cache_total() == before


def test_wave_width_no_recompile_second_round():
    """PR-7 fixed-shape contract: a second same-shape streaming-wave
    round re-uses every compiled wave executable."""
    from repro.core import dp as dp_mod
    from repro.core import privacy_engine as pe
    from repro.core import secure_agg as sa
    from repro.core.virtual_groups import make_virtual_groups

    rng = np.random.RandomState(0)
    cids = [f"c{i}" for i in range(8)]
    plan = make_virtual_groups(cids, 2, seed=0)
    scfg = sa.SecureAggConfig(wave_clients=4)
    dcfg = dp_mod.DPConfig()
    key = jax.random.PRNGKey(0)
    seed = jnp.asarray([1, 2], jnp.uint32)

    def round_once(stats=None):
        flat = jnp.asarray(rng.uniform(-1, 1, (8, 32)), jnp.float32)
        return pe.aggregate_flat(flat, plan, cids, seed, secure_cfg=scfg,
                                 dp_cfg=dcfg, key=key, stats=stats)

    stats = {}
    jax.block_until_ready(round_once(stats))   # warm the wave executables
    assert stats["stage2_route"] == "waved"
    before = tracing.jit_cache_total()
    jax.block_until_ready(round_once())
    assert tracing.jit_cache_total() == before


# ---------------------------------------------------------------------------
# typed metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("rounds", task=1).inc()
    reg.counter("rounds", task=1).inc(2.0)
    reg.counter("rounds", task=2).inc()            # distinct labels
    assert reg.value("rounds", task=1) == 3.0
    assert reg.value("rounds", task=2) == 1.0
    assert reg.value("missing", default=-1) == -1
    with pytest.raises(ValueError):
        reg.counter("rounds", task=1).inc(-1)

    reg.gauge("eps").set(2.5)
    reg.gauge("eps").set(3.5)                       # last value wins
    assert reg.value("eps") == 3.5

    h = reg.histogram("round_duration_s")
    assert h.edges == FIXED_BUCKETS["round_duration_s"]
    h.observe(0.01)    # first bucket (<= 0.05)
    h.observe(1.5)     # <= 2.0 bucket
    h.observe(1e6)     # overflow
    assert h.counts[0] == 1 and h.counts[-1] == 1
    assert h.count == 3
    assert reg.value("round_duration_s") == pytest.approx(
        (0.01 + 1.5 + 1e6) / 3)

    with pytest.raises(TypeError):
        reg.gauge("rounds", task=1)                 # kind conflict

    snap = reg.snapshot()
    names = [(r["name"], r["kind"]) for r in snap]
    assert ("rounds", "counter") in names and ("eps", "gauge") in names
    hrow = next(r for r in snap if r["kind"] == "histogram")
    assert hrow["count"] == 3 and len(hrow["buckets"]) == \
        len(hrow["edges"]) + 1
    json.dumps(snap)   # JSON-ready

    with pytest.raises(ValueError):
        reg.histogram("bad", edges=(2.0, 1.0))


def test_registry_pickles():
    reg = MetricsRegistry()
    reg.counter("c").inc(4)
    reg.histogram("h", edges=(1.0, 2.0)).observe(1.5)
    reg2 = pickle.loads(pickle.dumps(reg))
    assert reg2.value("c") == 4.0
    assert reg2.histogram("h", edges=(1.0, 2.0)).count == 1


# ---------------------------------------------------------------------------
# MetricsStore persistence (satellite: whole-store save/load)
# ---------------------------------------------------------------------------

def test_store_keeps_non_numeric_context():
    st = MetricsStore()
    st.log(1, 0, loss=0.5, stage2_route="waved", flag=True)
    rows = st._rows[1]
    assert {r["metric"]: r["value"] for r in rows} == \
        {"loss": 0.5, "stage2_route": "waved", "flag": 1.0}
    # series math sees only numerics
    assert st.series(1, "stage2_route") == ([], [])
    assert st.series(1, "loss") == ([0], [0.5])


def test_store_save_load_byte_identical(tmp_path):
    st = MetricsStore()
    st.log(1, 0, loss=0.9, n_selected=4, stage2_route="single_dispatch")
    st.log(1, 1, loss=0.7)
    st.log(3, 0, round_voided=1)
    host = {"platform": "test", "cpu_count": 2}
    p1 = str(tmp_path / "a.json")
    st.save(p1, now=1_700_000_000.123, host=host)

    loaded = MetricsStore.load(p1)
    assert loaded._rows[1] == st._rows[1]
    assert loaded._rows[3] == st._rows[3]
    assert sorted(loaded._rows) == [1, 3]          # int task keys restored
    assert loaded.header["version"] == 1
    assert loaded.header["host"] == host

    # byte-identical round trip with the header's clock/host re-injected
    p2 = str(tmp_path / "b.json")
    loaded.save(p2, now=loaded.header["saved_at_unix"],
                host=loaded.header["host"])
    assert open(p1, "rb").read() == open(p2, "rb").read()

    # and the loaded store still computes series/summaries
    assert loaded.latest(1, "loss") == 0.7
    assert loaded.churn_summary(3)["rounds_voided"] == 1


def test_store_save_header_defaults(tmp_path):
    st = MetricsStore()
    st.log(1, 0, loss=1.0)
    p = st.save(str(tmp_path / "s.json"))
    doc = json.load(open(p))
    assert doc["saved_at"].endswith("Z") and doc["saved_at_unix"] > 0
    assert "platform" in doc["host"] and "python" in doc["host"]


# ---------------------------------------------------------------------------
# bit-identity: tracing must never touch the math
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("profiler", [False, True],
                         ids=["collect", "profiler"])
def test_traced_round_bit_identical_to_untraced(profiler):
    from repro.core import dp as dp_mod
    from repro.core import secure_agg as sa
    from repro.core.orchestrator import run_sync_round_stacked
    from repro.core.strategies import make_strategy

    rng = np.random.RandomState(7)
    params = {"w": jnp.asarray(rng.uniform(-1, 1, 64), jnp.float32)}
    stacked = {"w": jnp.asarray(rng.uniform(-0.4, 0.4, (8, 64)),
                                jnp.float32)}
    cids = [f"c{i}" for i in range(8)]

    def run():
        strategy = make_strategy("fedavg")
        out, _, info = run_sync_round_stacked(
            params, strategy, strategy.init_state(params), cids, stacked,
            round_idx=0, vg_size=4,
            secure_cfg=sa.SecureAggConfig(),
            dp_cfg=dp_mod.DPConfig(mechanism="local", clip_norm=0.5,
                                   noise_multiplier=1.0),
            key=jax.random.PRNGKey(0))
        return np.asarray(out["w"]).view(np.uint32).tobytes(), info

    plain, _ = run()
    with tracing.use_tracer(tracing.Tracer(profiler=profiler)) as tr:
        traced, info = run()
    assert traced == plain
    assert info.stage2_route == "single_dispatch"
    # the chain's stage tree was recorded alongside identical bits
    names = {s.name for r in tr.roots() for s in _flatten(r)}
    assert {"vg_plan", "secure_agg", "ravel_rows", "cohort_interims",
            "limb_combine", "server_update"} <= names


# ---------------------------------------------------------------------------
# service integration: meters + flight events from a simulated task
# ---------------------------------------------------------------------------

def test_service_records_meters_and_flight(tmp_path):
    from repro.fl import (AttestationAuthority, ManagementService,
                          SimClient, TaskConfig)
    from repro.fl.simulator import run_sync_simulation

    svc = ManagementService()
    svc.flight = tracing.FlightRecorder(str(tmp_path / "flight"))
    tid = svc.create_task(
        TaskConfig("t", "a", "w", clients_per_round=2, n_rounds=2,
                   vg_size=2),
        {"w": jnp.zeros(8, jnp.float32)})
    auth = AttestationAuthority()
    clients = {}
    for i in range(4):
        cid = f"c{i}"
        assert svc.register_client(
            tid, cid, {"os": "linux", "n_samples": 10, "battery": 0.9},
            auth.issue(cid))
        clients[cid] = SimClient(
            cid, lambda blob, r: ({"w": np.full(8, 0.01, np.float32)},
                                  10, {"loss": 1.0}))

    with tracing.use_tracer(tracing.Tracer()) as tr:
        run_sync_simulation(svc, tid, clients)

    assert svc.meters.value("rounds_completed", task=tid) == 2.0
    assert svc.meters.value("jit_cache_misses") is not None
    assert svc.meters.histogram("round_duration_s", task=tid).count == 2

    events = svc.flight.read(tid)
    assert [e["round"] for e in events] == [0, 1]
    for ev in events:
        assert ev["event"] == "round"
        assert len(ev["cohort"]) == 2
        assert sorted(ev["survivors"]) == sorted(ev["cohort"])
        names = [s["name"] for s in ev["stages"]]
        assert names[0] == "aggregate" and "secure_agg" in names
    # the live span tree holds the full stage taxonomy for the same run
    span_names = {s.name for r in tr.roots() for s in _flatten(r)}
    assert {"round", "selection", "lease_acquire", "local_train",
            "aggregate", "secure_agg", "server_update"} <= span_names
