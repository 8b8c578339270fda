"""Churn subsystem: heterogeneous population model, over-provisioned
deadline selection + drop lifecycle, and dropout-tolerant secure
aggregation (Bonawitz-style mask recovery) — deterministic coverage.
The hypothesis sweep lives in tests/test_churn_property.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dp as dp_mod
from repro.core import dropout
from repro.core import privacy_engine as pe
from repro.core import secure_agg as sa
from repro.core.orchestrator import (ClientResult, _secure_mean_survivors,
                                     run_sync_round, run_sync_round_stacked)
from repro.core.quantize import quantize
from repro.core.strategies import FedAvg
from repro.core.virtual_groups import make_virtual_groups
from repro.fl import (AttestationAuthority, ManagementService,
                      PopulationConfig, TaskConfig, TaskStatus,
                      make_population_clients, population_summary,
                      sample_population)
from repro.fl.simulator import run_sync_simulation


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _mk_updates(rng, n, size=23):
    return {f"c{i:03d}": jnp.asarray(
        rng.uniform(-1.2, 1.2, size).astype(np.float32)) for i in range(n)}


def clean_survivor_reference(updates, cohort_sorted, plan, dropped, key,
                             scfg, dcfg):
    """Independent oracle: NO masks at all. Per-client DP through the same
    shared jitted row (key folded at the client's FULL-cohort position),
    quantize, plain per-group survivor code sums, shared master combine.
    Mask application + recovery must be an exact algebraic no-op relative
    to this."""
    fold_of = {c: j for j, c in enumerate(cohort_sorted)}
    interims, sizes = [], []
    for grp in plan.groups:
        surv = [c for c in grp.members if c not in dropped]
        if not surv:
            continue
        qsum = None
        for c in surv:
            u = updates[c]
            if dcfg.mechanism == "local":
                sg = float(dcfg.noise_multiplier * dcfg.clip_norm) \
                    if dcfg.noise_multiplier > 0 else 0.0
                u = dp_mod._flat_local_dp_jit(
                    u, jax.random.fold_in(key, fold_of[c]),
                    clip_norm=float(dcfg.clip_norm), sigma=sg)
            elif dcfg.mechanism == "global":
                u = dp_mod._flat_clip_jit(u,
                                          clip_norm=float(dcfg.clip_norm))
            q = quantize(u, scfg.clip, scfg.bits)
            qsum = q if qsum is None else qsum + q
        interims.append(qsum)
        sizes.append(len(surv))
    return sa.master_aggregate(interims, sizes, lambda x: x, scfg)


def _churn_both_paths(updates, cohort_sorted, plan, dropped, seed, key,
                      scfg, dcfg):
    """-> (serial survivor-protocol result, vectorized engine result)."""
    survivors = [c for c in cohort_sorted if c not in dropped]
    fold_of = {c: j for j, c in enumerate(cohort_sorted)}
    ser = _secure_mean_survivors({c: updates[c] for c in survivors}, plan,
                                 seed, key, scfg, dcfg, fold_of)
    size = updates[cohort_sorted[0]].shape[0]
    alive = np.asarray([c not in dropped for c in cohort_sorted])
    flat = jnp.stack([updates[c] if alive[j]
                      else jnp.zeros(size, jnp.float32)
                      for j, c in enumerate(cohort_sorted)])
    vec = pe.aggregate_flat(flat, plan, cohort_sorted, seed,
                            secure_cfg=scfg, dp_cfg=dcfg, key=key,
                            alive=alive)
    return ser, vec


# ---------------------------------------------------------------------------
# population model
# ---------------------------------------------------------------------------

class TestPopulation:
    def test_deterministic_from_seed(self):
        a = sample_population(40, seed=7)
        b = sample_population(40, seed=7)
        assert a == b
        c = sample_population(40, seed=8)
        assert a != c

    def test_tier_mix_and_speeds(self):
        pop = sample_population(500, seed=0)
        s = population_summary(pop)
        assert s["n"] == 500
        assert set(s["tiers"]) <= {"flagship", "midrange", "budget"}
        # midrange is the configured bulk of the default mix
        assert max(s["tiers"], key=s["tiers"].get) == "midrange"
        assert s["speed_min"] < 1.0 < s["speed_max"]

    def test_availability_window(self):
        cfg = PopulationConfig(avail_period=10.0, avail_duty=0.5)
        p = sample_population(1, seed=1, cfg=cfg)[0]
        ups = sum(p.available_at(t / 10.0) for t in range(200))
        assert 60 <= ups <= 140          # ~50% duty over two periods
        assert p.available_at(0.0) == p.available_at(p.avail_period)

    def test_dropout_hazard(self):
        cfg = PopulationConfig(mean_hazard=0.5)
        pop = sample_population(50, seed=2, cfg=cfg)
        assert any(p.dropout_hazard > 0 for p in pop)
        p = max(pop, key=lambda q: q.dropout_hazard)
        assert p.drop_probability(0.0) == 0.0
        assert 0.0 < p.drop_probability(1.0) < p.drop_probability(10.0) < 1.0
        safe = sample_population(5, seed=2)[0]     # mean_hazard = 0
        assert safe.drop_probability(1e9) == 0.0

    def test_make_population_clients(self):
        pop = sample_population(6, seed=3)
        clients = make_population_clients(pop)
        assert set(clients) == {p.client_id for p in pop}
        sc = clients[pop[0].client_id]
        assert sc.profile is pop[0]
        assert sc.device_info["tier"] == pop[0].tier


# ---------------------------------------------------------------------------
# selection lifecycle (satellite: drop/re-register)
# ---------------------------------------------------------------------------

def _mk_service_task(n_rounds=3, cpr=4, n_clients=8, **task_kw):
    svc = ManagementService()
    model = {"w": jnp.zeros(8, jnp.float32)}
    cfg = TaskConfig("t", "app", "wf", clients_per_round=cpr,
                     n_rounds=n_rounds, vg_size=2, **task_kw)
    tid = svc.create_task(cfg, model)
    auth = AttestationAuthority()
    for i in range(n_clients):
        cert = auth.issue(f"c{i}")
        assert svc.register_client(tid, f"c{i}", {"os": "linux",
                                                  "n_samples": 10,
                                                  "battery": 0.9}, cert)
    return svc, tid


class TestSelectionChurn:
    def test_two_round_drop_reregister_sequence(self):
        """A client dropped mid-round must (a) stop counting as available
        for the rest of the round and (b) return to the registered pool —
        selectable again — when the next round begins. Pre-fix, 'dropped'
        was sticky forever and stayed in the ready()/selection pool."""
        svc, tid = _mk_service_task(cpr=3, n_clients=4)
        task = svc.get_task(tid)
        _, cohort = svc.begin_round(tid)
        victim = cohort[0]
        svc.report_dropout(tid, victim)
        assert svc.selection.statuses(task)[victim] == "dropped"
        # dropped is OUT of the selectable pool and the ready() accounting
        assert victim not in svc.selection.available(task)
        assert not svc.selection.ready(task)   # 4 - 3 selected/dropped < 3
        for cid in cohort[1:]:
            svc.submit_update(tid, cid, {"w": jnp.ones(8) * 0.1}, 10)
        # next round: the dropped client re-registers and can be selected
        _, cohort2 = svc.begin_round(tid)
        assert svc.selection.statuses(task)[victim] in ("registered",
                                                        "selected")
        assert victim in set(svc.selection.available(task)) | set(cohort2)

    def test_overprovision_cohort_size(self):
        svc, tid = _mk_service_task(cpr=4, n_clients=8, overprovision=1.5)
        _, cohort = svc.begin_round(tid)
        assert len(cohort) == 6                 # ceil(4 * 1.5)

    def test_deadline_recorded(self):
        svc, tid = _mk_service_task(cpr=2, n_clients=4, round_timeout_s=9.5)
        task = svc.get_task(tid)
        svc.begin_round(tid)
        assert svc.selection.round_deadline(task) == 9.5

    def test_backfill_round_replaces_unavailable(self):
        svc, tid = _mk_service_task(cpr=4, n_clients=8)
        task = svc.get_task(tid)
        _, cohort = svc.begin_round(tid)
        gone = cohort[:2]
        repaired = svc.backfill_round(tid, gone)
        assert len(repaired) == len(cohort)
        assert not set(gone) & set(repaired)
        st = svc.selection.statuses(task)
        # released members are plain registered — NOT round dropouts
        assert all(st[c] == "registered" for c in gone)
        assert all(st[c] == "selected" for c in repaired)

    def test_backfill_after_submission_rejected(self):
        svc, tid = _mk_service_task(cpr=3, n_clients=6)
        _, cohort = svc.begin_round(tid)
        svc.submit_update(tid, cohort[0], {"w": jnp.ones(8) * 0.1}, 10)
        with pytest.raises(ValueError):
            svc.backfill_round(tid, [cohort[1]])

    def test_selection_availability_predicate(self):
        svc, tid = _mk_service_task(cpr=3, n_clients=6)
        _, cohort = svc.begin_round(
            tid, available=lambda cid: cid not in ("c0", "c1", "c2"))
        assert not {"c0", "c1", "c2"} & set(cohort)
        assert len(cohort) == 3


# ---------------------------------------------------------------------------
# mask recovery core
# ---------------------------------------------------------------------------

class TestRecoveryCore:
    def test_batched_corrections_match_serial(self):
        """The jitted batched reconstruction equals the per-pair python
        reference for every dropped member, including the pow2 padding
        rows (all-False alive mask -> exact zeros)."""
        g, size = 5, 13
        seed = jnp.asarray([3, 9], jnp.uint32)
        rs = jnp.asarray([7, 2], jnp.uint32)
        vg_ids = np.asarray([0, 1, 4], np.uint32)
        alive = np.asarray([[True, False, True, True, False],
                            [False, True, True, False, True],
                            [True, True, False, True, True]])
        d_idxs = np.asarray([1, 0, 2], np.uint32)
        corr = dropout._bucket_corrections(
            rs, jnp.asarray(np.concatenate([d_idxs, [0]])),
            jnp.asarray(np.concatenate([vg_ids, [0]])),
            jnp.asarray(np.concatenate([alive, np.zeros((1, g), bool)])),
            vg_size=g, size=size)
        assert corr.shape == (4, size)
        for r in range(3):
            gseed = sa.group_seed(rs, int(vg_ids[r]))
            surv = [i for i in range(g) if alive[r, i]]
            ref = dropout.dropped_net_mask([int(d_idxs[r])], surv, g,
                                           gseed, size)
            np.testing.assert_array_equal(np.asarray(corr[r]),
                                          np.asarray(ref))
        np.testing.assert_array_equal(np.asarray(corr[3]), 0)

    @pytest.mark.parametrize("n,vg,bits,mech,noise,drop", [
        (12, 4, 20, "off", 0.0, []),                 # |D| = 0
        (12, 4, 20, "off", 0.0, [3]),                # one straggler
        (13, 4, 18, "local", 0.8, [0, 5, 12]),       # ragged + DP noise
        (13, 4, 18, "local", 0.0, [2, 3]),           # clip-only
        (11, 3, 24, "global", 0.5, [1, 7]),          # global clip
        (12, 4, 20, "off", 0.0, [4, 5, 6, 7]),       # a WHOLE VG drops
        (8, 8, 20, "local", 0.5, [0, 1, 2, 3, 4, 5, 6]),  # 1 survivor
    ])
    def test_recovered_equals_clean_survivor_round(self, n, vg, bits, mech,
                                                   noise, drop):
        """Acceptance: for any dropped subset D (incl. a whole VG and the
        empty set), BOTH churn paths are bit-identical to the maskless
        clean reference over the survivors."""
        rng = np.random.RandomState(n * 31 + len(drop))
        updates = _mk_updates(rng, n)
        cohort = sorted(updates)
        plan = make_virtual_groups(cohort, vg, seed=5)
        # map drop positions (by row) to a dropped-cid set; drop whole-VG
        # cases address plan groups via membership, so translate by row
        dropped = {cohort[j] for j in drop}
        seed = jnp.asarray([11, 4], jnp.uint32)
        key = jax.random.PRNGKey(n)
        # min_survivors_per_vg=1: this test pins the exact-recovery math
        # for ANY survivor pattern (down to a single survivor), so the
        # privacy floor's group-voiding must be out of the way
        scfg = sa.SecureAggConfig(bits=bits, min_survivors_per_vg=1)
        dcfg = dp_mod.DPConfig(mechanism=mech, clip_norm=0.5,
                               noise_multiplier=noise)
        ser, vec = _churn_both_paths(updates, cohort, plan, dropped, seed,
                                     key, scfg, dcfg)
        ref = clean_survivor_reference(updates, cohort, plan, dropped, key,
                                       scfg, dcfg)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ser))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(vec))

    def test_prefix_path_corrupts_without_recovery(self):
        """Regression for the pre-churn protocol: summing only the
        survivors' payloads leaves the dropped member's pairwise masks
        NON-CANCELLING — the dequantized 'aggregate' is garbage. (This is
        why the old round had to abort on any straggler.)"""
        g, size, bits = 4, 16, 20
        seed = sa.group_seed(jnp.asarray([1, 2], jnp.uint32), 0)
        cfg = sa.SecureAggConfig(bits=bits)
        updates = [jnp.full(size, 0.25, jnp.float32) for _ in range(g)]
        payloads = [sa.client_protect(u, i, g, seed, cfg)[0]
                    for i, u in enumerate(updates)]
        # everyone submits: masks cancel, mean == 0.25
        full = sa.vg_aggregate(payloads)
        from repro.core.quantize import dequantize_sum
        np.testing.assert_allclose(
            np.asarray(dequantize_sum(full, g, cfg.clip, bits)), 0.25,
            atol=1e-4)
        # client 2 drops: the naive survivor sum is corrupted...
        naive = sa.vg_aggregate([payloads[i] for i in (0, 1, 3)])
        bad = dequantize_sum(naive, 3, cfg.clip, bits)
        assert not np.allclose(np.asarray(bad), 0.25, atol=0.05)
        # ...and recovery repairs it exactly
        fixed = naive + dropout.dropped_net_mask([2], [0, 1, 3], g, seed,
                                                 size)
        np.testing.assert_allclose(
            np.asarray(dequantize_sum(fixed, 3, cfg.clip, bits)), 0.25,
            atol=1e-4)

    def test_no_survivors_raises(self):
        rng = np.random.RandomState(0)
        updates = _mk_updates(rng, 4)
        cohort = sorted(updates)
        plan = make_virtual_groups(cohort, 2, seed=0)
        seed = jnp.asarray([1, 1], jnp.uint32)
        flat = jnp.stack([updates[c] for c in cohort])
        with pytest.raises(ValueError, match="no survivors"):
            pe.aggregate_flat(flat, plan, cohort, seed,
                              alive=np.zeros(4, bool))
        with pytest.raises(ValueError, match="no survivors"):
            sa.secure_aggregate_survivors({}, plan, seed)

    def test_recovery_stats_populated(self):
        rng = np.random.RandomState(1)
        updates = _mk_updates(rng, 8)
        cohort = sorted(updates)
        plan = make_virtual_groups(cohort, 4, seed=1)
        seed = jnp.asarray([2, 5], jnp.uint32)
        alive = np.ones(8, bool)
        alive[[1, 6]] = False
        flat = jnp.stack([updates[c] for c in cohort])
        stats = {}
        pe.aggregate_flat(flat, plan, cohort, seed, alive=alive,
                          stats=stats)
        assert stats["n_dropped"] == 2
        assert stats["recovery_s"] > 0.0


# ---------------------------------------------------------------------------
# round-level wiring
# ---------------------------------------------------------------------------

class TestChurnRounds:
    def _results(self, updates, survivors):
        return {c: ClientResult(update={"w": updates[c]}, n_samples=4,
                                metrics={"loss": 1.0}) for c in survivors}

    def test_run_sync_round_vectorized_matches_serial_under_churn(self):
        rng = np.random.RandomState(9)
        updates = _mk_updates(rng, 11)
        cohort = sorted(updates)
        survivors = [c for c in cohort if c not in {"c001", "c004", "c009"}]
        params = {"w": jnp.zeros(23, jnp.float32)}
        strat = FedAvg(server_lr=1.0)
        for dcfg in [dp_mod.DPConfig(),
                     dp_mod.DPConfig(mechanism="local", clip_norm=0.5,
                                     noise_multiplier=0.4),
                     dp_mod.DPConfig(mechanism="global", clip_norm=0.5,
                                     noise_multiplier=0.4)]:
            outs = {}
            for vect in (True, False):
                p, _, info = run_sync_round(
                    params, strat, strat.init_state(params),
                    self._results(updates, survivors),
                    round_idx=2, vg_size=4, cohort=cohort, dp_cfg=dcfg,
                    secure_cfg=sa.SecureAggConfig(
                        vectorized=vect, min_survivors_per_vg=1))
                outs[vect] = np.asarray(p["w"])
                assert info.n_selected == 11
                assert info.n_dropped == 3
                assert info.n_participants == 8
            np.testing.assert_array_equal(outs[True], outs[False])

    def test_stacked_churn_round_matches_dict_round(self):
        rng = np.random.RandomState(4)
        updates = _mk_updates(rng, 9)
        cohort = sorted(updates)
        survivors = [c for c in cohort if c not in {"c000", "c006"}]
        params = {"w": jnp.zeros(23, jnp.float32)}
        strat = FedAvg(server_lr=1.0)
        p_d, _, info_d = run_sync_round(
            params, strat, strat.init_state(params),
            self._results(updates, survivors),
            round_idx=1, vg_size=4, cohort=cohort)
        rev = list(reversed(survivors))   # stacked path re-sorts rows
        stacked = {"w": jnp.stack([updates[c] for c in rev])}
        p_s, _, info_s = run_sync_round_stacked(
            params, strat, strat.init_state(params), rev, stacked,
            [{"loss": 1.0}] * len(rev), round_idx=1, vg_size=4,
            cohort=cohort)
        np.testing.assert_array_equal(np.asarray(p_d["w"]),
                                      np.asarray(p_s["w"]))
        assert (info_d.n_selected, info_d.n_dropped) == \
            (info_s.n_selected, info_s.n_dropped) == (9, 2)

    def test_cohort_must_cover_results(self):
        rng = np.random.RandomState(2)
        updates = _mk_updates(rng, 4)
        params = {"w": jnp.zeros(23, jnp.float32)}
        strat = FedAvg(server_lr=1.0)
        with pytest.raises(ValueError, match="subset of cohort"):
            run_sync_round(params, strat, strat.init_state(params),
                           self._results(updates, sorted(updates)),
                           round_idx=0, vg_size=2,
                           cohort=sorted(updates)[:2])


# ---------------------------------------------------------------------------
# the stacked churn route: survivors' rows enter the chain in place
# ---------------------------------------------------------------------------

def _stacked_churn_case(n, vg, drop, seed=0):
    """-> (cohort, survivors, plan, dropped, survivor-only stacked pytree,
    the zero-filled full-cohort (n, size) rows)."""
    rng = np.random.RandomState(seed + n)
    cohort = [f"c{i:03d}" for i in range(n)]
    plan = make_virtual_groups(cohort, vg, seed=seed + 2)
    if drop == "void":
        # leave one survivor in the first group (voided at a threshold of
        # two) and drop the last cohort row besides
        grp = plan.groups[0].members
        dropped = set(grp[1:]) | {cohort[-1]}
    else:
        dropped = {cohort[j] for j in drop}
    survivors = [c for c in cohort if c not in dropped]
    rows = {c: (rng.uniform(-1.2, 1.2, 15).astype(np.float32),
                rng.uniform(-1.2, 1.2, 8).astype(np.float32))
            for c in cohort}
    stacked = {"a": jnp.asarray(np.stack([rows[c][0] for c in survivors])),
               "b": jnp.asarray(np.stack([rows[c][1] for c in survivors]))}
    full = np.stack([np.concatenate(rows[c]) if c not in dropped
                     else np.zeros(23, np.float32) for c in cohort])
    return cohort, survivors, plan, dropped, stacked, jnp.asarray(full)


class TestStackedChurnInPlace:
    @pytest.mark.parametrize("n,vg,mech,noise,drop,min_surv", [
        (12, 4, "off", 0.0, (0, 11), 1),             # first + last row
        (12, 4, "local", 0.6, (0, 11), 1),
        (12, 4, "global", 0.5, (0, 11), 1),
        (13, 4, "off", 0.0, (0, 6, 12), 1),          # two bucket sizes
        (13, 4, "local", 0.8, (0, 6, 12), 1),
        (13, 4, "global", 0.5, (1, 12), 1),
        (12, 4, "local", 0.4, "void", 2),            # a group voided
        (13, 4, "global", 0.5, "void", 2),
    ])
    def test_matches_zero_filled_cohort_bit_for_bit(self, n, vg, mech,
                                                    noise, drop, min_surv):
        """Survivor-only stacked rows through ``cohort_order`` equal the
        zero-filled full-cohort rows through ``aggregate_flat(alive=…)``
        to the bit, under no, local and global DP."""
        cohort, survivors, plan, dropped, stacked, full = \
            _stacked_churn_case(n, vg, drop)
        seed = jnp.asarray([5, 8], jnp.uint32)
        key = jax.random.PRNGKey(n)
        scfg = sa.SecureAggConfig(bits=20, min_survivors_per_vg=min_surv)
        dcfg = dp_mod.DPConfig(mechanism=mech, clip_norm=0.5,
                               noise_multiplier=noise)
        alive = np.asarray([c not in dropped for c in cohort])
        ref_stats, stats = {}, {}
        ref = pe.aggregate_flat(full, plan, cohort, seed, secure_cfg=scfg,
                                dp_cfg=dcfg, key=key, alive=alive,
                                stats=ref_stats)
        out = pe.aggregate_stacked(stacked, plan, survivors, seed,
                                   secure_cfg=scfg, dp_cfg=dcfg, key=key,
                                   cohort_order=cohort, stats=stats)
        got = np.concatenate([np.asarray(out["a"]), np.asarray(out["b"])])
        np.testing.assert_array_equal(got, np.asarray(ref))
        assert stats["n_dropped"] == ref_stats["n_dropped"]
        assert stats["n_voided_groups"] == ref_stats["n_voided_groups"] \
            == (1 if drop == "void" else 0)
        assert len({b.g for b in pe.plan_buckets(plan, cohort)}) == \
            (2 if n == 13 else 1)

    def test_churn_program_gets_survivor_rows_only(self, monkeypatch):
        cohort, survivors, plan, _, stacked, _ = \
            _stacked_churn_case(12, 4, (3, 7, 8))
        seen = []
        real = pe._cohort_interims_churn

        def record(flat, row_ids, *args, **kw):
            seen.append((flat.shape, row_ids.shape))
            return real(flat, row_ids, *args, **kw)

        monkeypatch.setattr(pe, "_cohort_interims_churn", record)
        pe.aggregate_stacked(stacked, plan, survivors,
                             jnp.asarray([1, 2], jnp.uint32),
                             cohort_order=cohort)
        assert seen == [((9, 23), (9,))]

    def test_same_survivor_count_reuses_the_executable(self):
        """Rounds that differ only in who dropped (and in the plan's
        shuffle) compile the churn program once."""
        size = 37                       # a shape no other test compiles
        cohort = [f"c{i:03d}" for i in range(12)]
        rng = np.random.RandomState(3)
        before = pe._cohort_interims_churn._cache_size()
        for r, drop in enumerate([(0, 5), (4, 11)]):
            survivors = [c for j, c in enumerate(cohort) if j not in drop]
            stacked = {"w": jnp.asarray(rng.uniform(
                -1, 1, (len(survivors), size)).astype(np.float32))}
            plan = make_virtual_groups(cohort, 4, seed=r)
            pe.aggregate_stacked(stacked, plan, survivors,
                                 jnp.asarray([r, 1], jnp.uint32),
                                 cohort_order=cohort)
            assert pe._cohort_interims_churn._cache_size() == before + 1

    def test_scatter_survivors_span_counts_mapped_rows(self):
        from repro import tracing
        cohort, survivors, plan, _, stacked, _ = \
            _stacked_churn_case(12, 4, (2, 9))
        with tracing.use_tracer(tracing.Tracer()) as tr:
            pe.aggregate_stacked(stacked, plan, survivors,
                                 jnp.asarray([3, 1], jnp.uint32),
                                 cohort_order=cohort)
        (root,) = tr.find_roots("secure_agg")
        (sp,) = [c for c in root.children if c.name == "scatter_survivors"]
        assert sp.attrs["mapped_rows"] == len(survivors) == 10
        assert "h2d_bytes" not in sp.attrs


# ---------------------------------------------------------------------------
# service layer + simulator
# ---------------------------------------------------------------------------

class TestServiceChurn:
    def test_round_no_longer_aborts_on_straggling_vg(self):
        """The headline behaviour: dropouts reported mid-round, the round
        completes over the survivors, and per-client vs bulk survivor
        submission produce the SAME model."""
        rng = np.random.RandomState(0)
        ups = {f"c{i}": jnp.asarray(rng.uniform(-0.2, 0.2, 8), jnp.float32)
               for i in range(8)}
        models = {}
        for path in ("per-client", "bulk"):
            svc, tid = _mk_service_task(n_rounds=1, cpr=6, n_clients=8)
            _, cohort = svc.begin_round(tid)
            dropped = cohort[:2]
            survivors = [c for c in cohort if c not in dropped]
            for cid in dropped:
                assert not svc.report_dropout(tid, cid)
            if path == "per-client":
                done = [svc.submit_update(tid, c, {"w": ups[c]}, 10,
                                          {"loss": 1.0})
                        for c in survivors]
                assert done == [False] * (len(survivors) - 1) + [True]
            else:
                stacked = {"w": jnp.stack([ups[c] for c in survivors])}
                assert svc.submit_cohort(tid, survivors, stacked, 10,
                                         [{"loss": 1.0}] * len(survivors))
            task = svc.get_task(tid)
            assert task.status is TaskStatus.COMPLETED
            h = task.history[-1]
            assert (h["n_selected"], h["n_survived"], h["n_dropped"]) == \
                (6, 4, 2)
            assert h["recovery_s"] >= 0.0
            models[path] = np.asarray(task.model["w"])
        np.testing.assert_array_equal(models["per-client"], models["bulk"])

    def test_dropout_report_completes_round(self):
        """A dropout report arriving LAST (after every survivor submitted)
        completes the round too — order independence."""
        svc, tid = _mk_service_task(n_rounds=1, cpr=4, n_clients=6)
        _, cohort = svc.begin_round(tid)
        for cid in cohort[1:]:
            assert not svc.submit_update(tid, cid, {"w": jnp.ones(8) * 0.1},
                                         10)
        assert svc.report_dropout(tid, cohort[0])
        assert svc.get_task(tid).status is TaskStatus.COMPLETED

    def test_all_dropped_voids_round(self):
        svc, tid = _mk_service_task(n_rounds=1, cpr=3, n_clients=6)
        ri, cohort = svc.begin_round(tid)
        closed = [svc.report_dropout(tid, cid) for cid in cohort]
        # the LAST report closes (voids) the round
        assert closed == [False] * (len(cohort) - 1) + [True]
        task = svc.get_task(tid)
        assert task.status is TaskStatus.RUNNING      # round NOT consumed
        assert task.round_idx == ri
        # the next round re-selects (dropped members back in the pool)
        _, cohort2 = svc.begin_round(tid)
        assert len(cohort2) == 3
        assert svc.metrics.latest(tid, "round_voided") == 1.0

    def test_late_retry_cannot_rerun_closed_round(self):
        """A dropout report closes the round; a survivor's duplicate
        upload arriving after that must be rejected, not re-run the whole
        aggregation (double model step + double accountant count)."""
        # min_survivors_per_vg=1: the round must CLOSE via a 1-survivor
        # aggregation (under the default floor it would be voided instead,
        # which exercises a different path)
        svc, tid = _mk_service_task(
            n_rounds=2, cpr=2, n_clients=4,
            secure_agg=sa.SecureAggConfig(min_survivors_per_vg=1))
        _, cohort = svc.begin_round(tid)
        assert not svc.submit_update(tid, cohort[0],
                                     {"w": jnp.ones(8) * 0.1}, 10)
        assert svc.report_dropout(tid, cohort[1])     # closes the round
        task = svc.get_task(tid)
        assert task.round_idx == 1
        model_after = np.asarray(task.model["w"]).copy()
        # the straggling retry: same client, same round — must be a no-op
        assert not svc.submit_update(tid, cohort[0],
                                     {"w": jnp.ones(8) * 0.1}, 10)
        assert task.round_idx == 1
        np.testing.assert_array_equal(np.asarray(task.model["w"]),
                                      model_after)

    def test_dropped_client_submission_rejected(self):
        svc, tid = _mk_service_task(n_rounds=1, cpr=3, n_clients=6)
        _, cohort = svc.begin_round(tid)
        svc.report_dropout(tid, cohort[0])
        assert not svc.submit_update(tid, cohort[0],
                                     {"w": jnp.ones(8) * 0.1}, 10)
        # and a second report is a no-op
        assert not svc.report_dropout(tid, cohort[0])

    def test_accountant_uses_realized_participation(self):
        """Over-provisioned rounds aggregate MORE than clients_per_round
        clients; the RDP accountant must compose at the realized rate
        (survivors / pool), not the config target — else epsilon is
        under-reported."""
        from repro.core.dp import DPConfig, compute_rdp, get_privacy_spent
        dp = DPConfig(mechanism="local", clip_norm=0.5,
                      noise_multiplier=1.0)
        svc, tid = _mk_service_task(n_rounds=1, cpr=4, n_clients=8,
                                    overprovision=1.5, dp=dp)
        _, cohort = svc.begin_round(tid)          # 6 selected, all survive
        for cid in cohort:
            svc.submit_update(tid, cid, {"w": jnp.ones(8) * 0.1}, 10)
        exp_eps, _ = get_privacy_spent(compute_rdp(6 / 8, 1.0, steps=1),
                                       dp.delta)
        assert svc.epsilon(tid) == pytest.approx(exp_eps, rel=1e-9)
        wrong_eps, _ = get_privacy_spent(compute_rdp(4 / 8, 1.0, steps=1),
                                         dp.delta)
        assert abs(svc.epsilon(tid) - wrong_eps) > 1e-9

    def test_churn_summary_and_dashboard(self):
        svc, tid = _mk_service_task(n_rounds=2, cpr=4, n_clients=8,
                                    overprovision=1.25)
        for _ in range(2):
            _, cohort = svc.begin_round(tid)
            svc.report_dropout(tid, cohort[0])
            for cid in cohort[1:]:
                svc.submit_update(tid, cid, {"w": jnp.ones(8) * 0.1}, 10)
        s = svc.metrics.churn_summary(tid)
        assert s["rounds"] == 2
        assert s["selected"] == 10 and s["dropped"] == 2
        assert s["survived"] == 8
        assert 0 < s["dropout_rate"] < 1
        from repro.fl.dashboard import render_task_view
        view = render_task_view(svc, tid)
        assert "churn:" in view and "dropped=2" in view


class TestSimulatorChurn:
    def _trainer_factory(self, i):
        def trainer(blob, rnd):
            return {"w": jnp.ones(8, jnp.float32) * 0.05}, 10, {"loss": 1.0}
        return trainer

    def test_population_sim_completes_under_churn(self):
        pop = sample_population(
            14, seed=3, cfg=PopulationConfig(mean_hazard=0.1,
                                             avail_duty=0.75,
                                             avail_period=8.0))
        clients = make_population_clients(pop, self._trainer_factory)
        svc = ManagementService()
        cfg = TaskConfig("t", "app", "wf", clients_per_round=4, n_rounds=4,
                         vg_size=2, overprovision=1.5, round_timeout_s=2.0)
        tid = svc.create_task(cfg, {"w": jnp.zeros(8, jnp.float32)})
        res = run_sync_simulation(svc, tid, clients, seed=1)
        task = svc.get_task(tid)
        assert task.status is TaskStatus.COMPLETED
        assert task.round_idx == 4
        assert res.n_dropped_total >= 1          # hazard 0.1 over 14 devices
        # dropouts cost the deadline; every duration is bounded by it
        assert all(d <= 2.0 + 0.05 + 1e-9 for d in res.round_durations)
        s = svc.metrics.churn_summary(tid)
        assert s["dropped"] == res.n_dropped_total
        assert s["survived"] + s["dropped"] == s["selected"]

    def test_sim_idles_through_closed_availability_windows(self):
        """A momentarily-unreachable fleet must not end the run: the loop
        idles one deadline and re-selects once windows reopen."""
        from repro.fl.population import DeviceProfile
        from repro.fl.simulator import SimClient
        clients = {}
        for i in range(4):
            cid = f"c{i}"
            # window phase < 3 of a 10s period, offset 5: CLOSED at t=0,
            # open at t=6 (one idle deadline later)
            prof = DeviceProfile(cid, "midrange", 1.0, 0.5, 0.0,
                                 5.0, 10.0, 0.3)
            clients[cid] = SimClient(cid, self._trainer_factory(i),
                                     base_train_s=0.5, profile=prof)
        svc = ManagementService()
        cfg = TaskConfig("t", "app", "wf", clients_per_round=2, n_rounds=2,
                         vg_size=2, round_timeout_s=6.0)
        tid = svc.create_task(cfg, {"w": jnp.zeros(8, jnp.float32)})
        res = run_sync_simulation(svc, tid, clients, seed=0)
        assert svc.get_task(tid).status is TaskStatus.COMPLETED
        assert res.n_server_steps == 2
        assert res.total_time > 6.0          # idled at least one deadline

    def test_no_profiles_means_no_churn_path(self):
        """Without device profiles (and overprovision 1.0) the simulator
        must take the original loop — byte-identical legacy behaviour."""
        from repro.fl.simulator import SimClient
        clients = {f"c{i}": SimClient(f"c{i}", self._trainer_factory(i))
                   for i in range(6)}
        svc = ManagementService()
        cfg = TaskConfig("t", "app", "wf", clients_per_round=4, n_rounds=2,
                         vg_size=2)
        tid = svc.create_task(cfg, {"w": jnp.zeros(8, jnp.float32)})
        res = run_sync_simulation(svc, tid, clients, seed=0)
        assert svc.get_task(tid).status is TaskStatus.COMPLETED
        assert res.n_dropped_total == 0
        assert all("n_dropped" not in h or h["n_dropped"] == 0
                   for h in svc.get_task(tid).history)
