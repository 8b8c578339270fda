"""Vectorized sync-round privacy pipeline: the paper's whole §4 chain —
per-client DP clip/noise -> quantize -> pairwise mask -> stage-1 VG modular
sums — as ONE jitted computation over the cohort's stacked flat updates.

The serial reference (``secure_agg.secure_aggregate_round`` plus the
per-client DP loop in ``orchestrator.run_sync_round``) dispatches O(n_clients)
python-level jnp calls per round; production FL treats exactly this path as
the server's throughput-critical hot loop. Here the cohort is an
``(n_clients, size)`` array and every stage is vmapped, so the full pipeline
is one XLA program (two at most — see bucketing) regardless of cohort size.

Ragged Virtual-Group plans are handled by SIZE-BUCKETING: ``make_virtual_
groups`` merges a trailing remainder < min_vg_size into the previous group,
so a plan contains at most TWO distinct group sizes — i.e. at most two
compiled shapes. Only the bucket GEOMETRY (group size, group count) is a
static jit argument; the per-round client permutation and group ids are
traced arrays, so successive rounds (which reshuffle clients) reuse the
same compiled program. Within a bucket,
masking reuses ``masking.net_mask_traced`` via ``protect_cohort_grouped``
(pure-jnp path) or the batched Pallas kernel ``kernels.ops.mask_apply_cohort``
(``use_kernels=True``).

Bit-exactness contract (hypothesis-tested in tests/test_privacy_engine.py):
the engine's output is bit-identical to the serial reference. The integer
stages (quantize codes, masks, wrapping sums, stage-2 limb states) are exact
by construction; the float stages (DP rows, the stage-2 dequantize tail) are
shared JITTED functions on both paths, because XLA FMA-contracts the
clip/noise and dequantize chains — an eager reference would differ from any
jitted pipeline by ulps. The big jit therefore returns exact integer
per-shard limb states and the final dequantize runs in the same standalone
``secure_agg._finalize_jit`` executable the serial master uses.

Stage 2 is the hierarchical limb-state combine of ``repro.core.quantize``:
the cohort's VGs split into disjoint pod shards, each folded to a canonical
base-2^16 limb state inside the big jit (exact for < 2^16 VGs per shard),
merged exactly across < 2^16 shards, then dequantized once — lifting the
old single-tier 2^16-VG cap to ~2^32 VGs with bit-identical results at any
shard count (``SecureAggConfig.limbs=4`` adds a 2^48 lane for plans past
that). (The pre-PR-2 master summed interims in raw uint32 and silently
wrapped once bits + ceil(log2(total_cohort)) > 32.)

CHURN: ``aggregate_flat(alive=...)`` / ``aggregate_stacked(cohort_order=
...)`` run the same pipeline when part of the selected cohort dropped
mid-round — survivor-only group sums (payloads still carry FULL masks),
one batched mask-recovery call (``repro.core.dropout``), then the shared
combine over |S|. Bit-identical to a clean round over the survivors.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core import dp as dp_mod
from repro.core import masking
from repro.core import raveling
from repro.core.kdf import U32
from repro.core.quantize import (check_headroom, check_master_headroom,
                                 check_shard_headroom, interim_limb_state,
                                 quantize, shard_limb_states)
from repro.core.secure_agg import (AggregationRefused, SecureAggConfig,
                                   _shard_limbs_jit, combine_limb_states,
                                   group_seed, resolve_master_shards)
from repro import tracing  # stdlib-only; safe for core to depend on


@dataclass(frozen=True)
class BucketSpec:
    """Host-side layout of all virtual groups sharing one size. Only
    (g, n_groups) reaches jit as a static; rows/vg_ids are shipped as
    traced arrays so per-round reshuffles don't recompile.

    ``rows[m * g + i]`` is the stack-row of member ``i`` of the bucket's
    ``m``-th group (protocol order within the group)."""
    g: int          # group size
    vg_ids: tuple   # plan vg_ids of the bucket's groups, plan order
    rows: tuple     # flat row indices into the (n_clients, size) stack

    @property
    def n_groups(self) -> int:
        return len(self.vg_ids)


def plan_buckets(plan, client_order) -> tuple:
    """Bucket a VGPlan's groups by size against a stack ordering.

    ``client_order``: the client ids of the stacked update rows, row order.
    The merge rule in ``make_virtual_groups`` yields at most two distinct
    sizes, so this returns at most two buckets (sorted by size)."""
    row_of = {cid: j for j, cid in enumerate(client_order)}
    if len(row_of) != len(client_order):
        raise ValueError("duplicate client ids in stacked cohort")
    by_size: dict = {}
    for grp in plan.groups:
        by_size.setdefault(len(grp.members), []).append(grp)
    buckets = []
    for g in sorted(by_size):
        groups = by_size[g]
        buckets.append(BucketSpec(
            g=g,
            vg_ids=tuple(grp.vg_id for grp in groups),
            rows=tuple(row_of[cid] for grp in groups
                       for cid in grp.members)))
    return tuple(buckets)


def _interims_body(flat, round_seed, key, rows_t, vgs_t, alive,
                   bucket_shapes, secure_cfg, dp_cfg):
    """Shared trace body: (n, size) f32 stacked updates -> (G, size)
    uint32 per-VG wrapping sums, bucket order.

    ``alive``: None (every row submits — the churn-free path compiles
    with no extra ops) or a traced (n,) bool row mask: each SURVIVOR's
    payload still carries its FULL net mask (clients masked before drops
    were known), dropped rows are zeroed before the group sums, and the
    caller repairs the non-cancelling residual via
    ``dropout.recover_interims``. DP/quantize run on every row either
    way, so a survivor's code — key-folded at its FULL-cohort row — is
    bit-identical whether or not anyone else dropped."""
    n = flat.shape[0]
    # per-client DP, vmapped over the client axis; key folding follows the
    # row order (== sorted-cid order in the orchestrator), matching the
    # serial reference's fold_in(key, j) exactly.
    flat = _dp_rows(flat, jnp.arange(n, dtype=jnp.uint32), key, dp_cfg)
    with jax.named_scope("quantize"):
        qs = quantize(flat, secure_cfg.clip, secure_cfg.bits)  # (n, size)

    interims = []
    for (g, m), rows, vgs in zip(bucket_shapes, rows_t, vgs_t):
        masked = _mask_rows(qs[rows], round_seed, vgs, g, m, secure_cfg)
        with jax.named_scope("vg_sum"):
            if alive is not None:
                masked = jnp.where(alive[rows][:, None], masked,
                                   jnp.zeros((), U32))
            interims.append(masking.vg_sums(masked, g))     # (m, size)
    return jnp.concatenate(interims, axis=0)                # (G, size)


def _dp_rows(flat, row_ids, key, dp_cfg):
    """Stage 1 of the chain, named ``dp`` on the device: per-client DP on
    (n, size) rows, the key folded at each row's global ``row_ids``."""
    with jax.named_scope("dp"):
        flat = flat.astype(jnp.float32)
        if dp_cfg.mechanism == "local":
            sigma = float(dp_cfg.noise_multiplier * dp_cfg.clip_norm) \
                if dp_cfg.noise_multiplier > 0 else 0.0
            keys = jax.vmap(lambda j: jax.random.fold_in(key, j))(row_ids)
            flat = jax.vmap(partial(dp_mod.flat_local_dp,
                                    clip_norm=float(dp_cfg.clip_norm),
                                    sigma=sigma))(flat, keys)
        elif dp_cfg.mechanism == "global":
            # clip here; the server-side noise is added to the combined
            # mean by the orchestrator (one draw, not a per-client stage)
            flat = jax.vmap(partial(dp_mod.flat_clip,
                                    clip_norm=float(dp_cfg.clip_norm)))(flat)
        return flat


def _mask_rows(qb, round_seed, vgs, g, m, secure_cfg):
    """Stage 3, named ``mask`` on the device: the pairwise masks of ``m``
    whole groups of ``g`` rows (``qb`` group-major, ``vgs`` their plan
    ids)."""
    with jax.named_scope("mask"):
        gseeds = jnp.repeat(
            jax.vmap(lambda v: group_seed(round_seed, v))(vgs),
            g, axis=0)                                      # (m*g, 2)
        idxs = jnp.tile(jnp.arange(g, dtype=U32), m)
        if secure_cfg.use_kernels:
            from repro.kernels import ops
            return ops.mask_apply_cohort(qb, idxs, gseeds, g)
        return masking.protect_cohort_grouped(qb, idxs, gseeds, g)


@partial(jax.jit,
         static_argnames=("bucket_shapes", "n_shards", "secure_cfg",
                          "dp_cfg"))
def _cohort_interims(flat, round_seed, key, rows_t, vgs_t, *,
                     bucket_shapes, n_shards, secure_cfg, dp_cfg):
    """The one compiled call: (n, size) f32 stacked updates -> exact
    (n_shards, n_limbs, size) uint32 per-shard stage-2 limb states
    (``quantize.interim_limb_state`` over disjoint VG shards, bucket
    order; zero-row padding on the last shard is a no-op in the integer
    sums).

    ``bucket_shapes``: tuple of (g, n_groups) per bucket — with
    ``n_shards`` the only plan-dependent statics; the per-round
    permutation (``rows_t`` row indices, ``vgs_t`` group ids) is traced,
    so rounds with the same cohort/bucket geometry hit the jit cache even
    though ``make_virtual_groups`` reshuffles clients every round."""
    stacked = _interims_body(flat, round_seed, key, rows_t, vgs_t, None,
                             bucket_shapes, secure_cfg, dp_cfg)
    # pod-shard axis: fold each disjoint VG shard into its limb state
    # INSIDE this jit (tier 1, exact); the cross-shard merge + float tail
    # run in the shared executables outside (aggregate_flat).
    return shard_limb_states(stacked, n_shards, secure_cfg.limbs)


@partial(jax.jit,
         static_argnames=("bucket_shapes", "secure_cfg", "dp_cfg"))
def _cohort_interims_churn(flat, round_seed, key, rows_t, vgs_t, alive, *,
                           bucket_shapes, secure_cfg, dp_cfg):
    """Churn twin of :func:`_cohort_interims`: survivor-only group sums
    returned RAW (G, size) — mask recovery scatter-adds onto them before
    the limb fold, so the fold runs outside this jit. ``alive`` is a
    traced row mask; rounds that only differ in WHO dropped reuse the
    executable."""
    return _interims_body(flat, round_seed, key, rows_t, vgs_t, alive,
                          bucket_shapes, secure_cfg, dp_cfg)


@partial(jax.jit, static_argnames=("g", "secure_cfg", "dp_cfg"))
def _wave_limb_state(wave_flat, row_ids, round_seed, key, vgs, real, *,
                     g, secure_cfg, dp_cfg):
    """One streaming wave: a fixed-width chunk of whole virtual groups ->
    its exact stage-2 limb state. The wave scheduler's compiled unit.

    ``wave_flat``: (m*g, size) f32 — the wave's rows gathered group-major
    on the host; ``row_ids``: (m*g,) uint32 GLOBAL stack rows — the DP key
    folds at the same ``fold_in(key, row)`` values as the single-dispatch
    ``_interims_body``, so a client's noised row is bit-identical in any
    wave; ``vgs``: (m,) uint32 plan group ids; ``real``: (m,) bool — the
    last wave pads to the fixed width by repeating its final group, and
    pad groups' interims are zeroed before the limb fold (zero rows are
    exact no-ops in the integer sums), so one compiled shape serves every
    wave. The per-stage math is the ``_interims_body`` chain verbatim;
    limb digits are shard-layout independent, so stacking wave states and
    merging through the shared executables is bit-identical to the
    whole-cohort dispatch."""
    m = vgs.shape[0]
    flat = _dp_rows(wave_flat, row_ids, key, dp_cfg)
    with jax.named_scope("quantize"):
        qs = quantize(flat, secure_cfg.clip, secure_cfg.bits)
    masked = _mask_rows(qs, round_seed, vgs, g, m, secure_cfg)
    with jax.named_scope("vg_sum"):
        interims = masking.vg_sums(masked, g)               # (m, size)
        interims = jnp.where(real[:, None], interims, jnp.zeros((), U32))
    return interim_limb_state(interims, secure_cfg.limbs)


def _waved_states(flat, buckets, round_seed, key, wave, secure_cfg, dp_cfg):
    """Stream the cohort through ~``wave``-client compiled waves of whole
    virtual groups -> (n_waves, n_limbs, size) exact per-wave limb states.

    ``flat`` stays on the HOST; only one wave's rows transfer per dispatch
    — the OOM posture that lets a 65k-client cohort run through a
    4096-wide executable. At most one compiled shape per bucket (two per
    plan, like the single-dispatch path)."""
    flat = np.asarray(flat, np.float32)
    states = []
    for b in buckets:
        m_w = max(1, wave // b.g)          # whole groups per wave
        check_master_headroom(m_w)
        rows = np.asarray(b.rows, np.int64).reshape(b.n_groups, b.g)
        vgs = np.asarray(b.vg_ids, np.uint32)
        for s in range(0, b.n_groups, m_w):
            chunk = rows[s:s + m_w]
            cv = vgs[s:s + m_w]
            m_real = chunk.shape[0]
            if m_real < m_w:               # pad to the fixed wave shape
                pad = m_w - m_real
                chunk = np.concatenate([chunk,
                                        np.repeat(chunk[-1:], pad, axis=0)])
                cv = np.concatenate([cv, np.repeat(cv[-1:], pad)])
            with tracing.span("wave", wave=len(states), g=b.g,
                              n_groups=m_real) as sp:
                rows_w = flat[chunk.ravel()]
                sp.transfer(h2d=rows_w)
                states.append(_wave_limb_state(
                    jnp.asarray(rows_w),
                    jnp.asarray(chunk.ravel().astype(np.uint32)),
                    round_seed, key, jnp.asarray(cv),
                    jnp.asarray(np.arange(m_w) < m_real),
                    g=b.g, secure_cfg=secure_cfg, dp_cfg=dp_cfg))
    return jnp.stack(states)


@jax.jit
def ravel_rows(stacked_updates):
    """Stacked pytree (leaves (n, ...)) -> (n, size) f32, in-jit (the fused
    entries — sync cohort and async buffer — never unstack to host)."""
    return jax.vmap(
        lambda t: ravel_pytree(t)[0].astype(jnp.float32))(stacked_updates)


def stack_flat_updates(updates):
    """[update pytree, ...] -> ((n, size) device array, unflatten fn).

    Host-side np staging (one transfer, not n_leaves * n transfers) for the
    orchestrator path whose inputs are per-client host pytrees. The
    unflatten closure is cached by treedef+shapes (``repro.core.raveling``)
    instead of being rebuilt — with a throwaway data ravel — every round."""
    rows = []
    for u in updates:
        rows.append(np.concatenate(
            [np.asarray(leaf, np.float32).ravel()
             for leaf in jax.tree.leaves(u)]))
    _, unflatten = raveling.cached_unflatten(updates[0])
    return jnp.asarray(np.stack(rows)), unflatten


def _check_plan(buckets, secure_cfg, n_shards=None) -> int:
    """Headroom guards for a bucketed plan; returns the resolved stage-2
    shard count (tier-1 per-shard and tier-2 cross-shard bounds both
    enforced by ``resolve_master_shards``)."""
    for b in buckets:
        check_headroom(secure_cfg.bits, b.g)
    return resolve_master_shards(sum(b.n_groups for b in buckets),
                                 secure_cfg, n_shards)


def aggregate_flat(flat, plan, client_order, round_seed, *,
                   secure_cfg: SecureAggConfig = SecureAggConfig(),
                   dp_cfg: dp_mod.DPConfig = dp_mod.DPConfig(),
                   key=None, n_shards=None, alive=None, stats=None):
    """Full pipeline over pre-flattened rows -> (size,) f32 cohort mean.

    ``n_shards`` (or ``secure_cfg.master_shards``) shards the stage-2
    combine across per-pod limb-state accumulators — required past 2^16
    VGs, bit-identical at any legal count (auto-resolved by default).

    ``alive``: optional (n,) host bool array — the churn path. False rows
    are clients that were SELECTED into the plan (their peers' payloads
    carry mask terms for them) but never submitted; their rows in ``flat``
    are ignored (feed zeros). Survivor group sums are repaired by
    ``dropout.recover_interims`` and the mean divides by |S| — the guards
    and the dequantize retarget to the survivor count, and the result is
    bit-identical to a clean round over the survivors (same DP key-fold
    rows). ``stats``: optional dict, receives ``n_dropped``/``recovery_s``
    from the recovery step."""
    with tracing.span("secure_agg", n=flat.shape[0]) as sp:
        return _aggregate_flat(sp, flat, plan, client_order, round_seed,
                               secure_cfg, dp_cfg, key, n_shards, alive,
                               stats)


def _aggregate_flat(sp, flat, plan, client_order, round_seed, secure_cfg,
                    dp_cfg, key, n_shards, alive, stats):
    """:func:`aggregate_flat` inside its open ``secure_agg`` span ``sp``,
    which it tags with the stage-2 route."""
    with tracing.span("vg_plan", n=flat.shape[0]):
        buckets = plan_buckets(plan, client_order)
        n_shards = _check_plan(buckets, secure_cfg, n_shards)
        rows_t = tuple(jnp.asarray(b.rows, jnp.int32) for b in buckets)
        vgs_t = tuple(jnp.asarray(b.vg_ids, U32) for b in buckets)
    bucket_shapes = tuple((b.g, b.n_groups) for b in buckets)
    n = flat.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    round_seed = jnp.asarray(round_seed, U32)
    if alive is None:
        wave = int(getattr(secure_cfg, "wave_clients", 0))
        if 0 < wave < n:
            # streaming-wave route: same per-row math, fixed-width
            # compiled waves, exact partial limb folds (bit-identical —
            # limb digits are layout-independent and the float tail is
            # the same shared executable)
            if stats is not None:
                stats["stage2_route"] = "waved"
            sp.set(route="waved", n_shards=n_shards)
            sp.transfer(d2h=flat)
            states = _waved_states(flat, buckets, round_seed, key,
                                   wave, secure_cfg, dp_cfg)
            check_shard_headroom(states.shape[0])
            with tracing.span("limb_combine",
                              n_states=int(states.shape[0])):
                return combine_limb_states(states, n, secure_cfg)
        if stats is not None:
            stats["stage2_route"] = "single_dispatch"
        sp.set(route="single_dispatch", n_shards=n_shards)
        with tracing.span("cohort_interims", n=n) as isp:
            isp.transfer(h2d=flat)
            states = _cohort_interims(
                jnp.asarray(flat), round_seed, key, rows_t, vgs_t,
                bucket_shapes=bucket_shapes, n_shards=n_shards,
                secure_cfg=secure_cfg, dp_cfg=dp_cfg)
        with tracing.span("limb_combine", n_shards=n_shards):
            return combine_limb_states(states, n, secure_cfg)

    from repro.core import dropout
    alive = np.asarray(alive, bool)
    if alive.shape[0] != n:
        raise ValueError(f"alive mask has {alive.shape[0]} rows for "
                         f"{n} clients")
    if not alive.any():
        raise AggregationRefused(
            "no survivors: every selected client dropped — nothing to "
            "aggregate")
    # min-survivor refusal (mirrors the serial loop's `continue`): a group
    # whose survivor count drops below the threshold is VOIDED by marking
    # its remaining rows dead — a fully-dead group's recovered interim is
    # an exact-zero row, so voiding here is bit-identical to skipping the
    # group serially, and the mean's divisor shrinks with it.
    min_surv = int(getattr(secure_cfg, "min_survivors_per_vg", 1))
    n_voided_groups = 0
    if min_surv > 1 and not alive.all():
        alive = alive.copy()
        for b in buckets:
            rows_m = np.asarray(b.rows, np.int64).reshape(b.n_groups, b.g)
            counts = alive[rows_m].sum(axis=1)
            void = (counts > 0) & (counts < min_surv)
            if void.any():
                n_voided_groups += int(void.sum())
                alive[rows_m[void].ravel()] = False
        if not alive.any():
            raise AggregationRefused(
                "round refused: every surviving virtual group fell below "
                f"min_survivors_per_vg={min_surv}")
    if stats is not None:
        stats["n_voided_groups"] = n_voided_groups
        stats["stage2_route"] = "churn_recovery"
    n_survivors = int(alive.sum())
    sp.set(route="churn_recovery", n_survivors=n_survivors,
           n_shards=n_shards)
    with tracing.span("cohort_interims", n=n, churn=True) as isp:
        isp.transfer(h2d=flat)
        interims = _cohort_interims_churn(
            jnp.asarray(flat), round_seed, key, rows_t, vgs_t,
            jnp.asarray(alive), bucket_shapes=bucket_shapes,
            secure_cfg=secure_cfg, dp_cfg=dp_cfg)
    with tracing.span("mask_recovery", n_dropped=n - n_survivors):
        interims = dropout.recover_interims(interims, buckets, alive,
                                            round_seed, stats=stats)
    with tracing.span("limb_combine", n_shards=n_shards):
        states = _shard_limbs_jit(interims, n_shards, secure_cfg.limbs)
        return combine_limb_states(states, n_survivors, secure_cfg)


def aggregate_stacked(stacked_updates, plan, client_order, round_seed, *,
                      secure_cfg: SecureAggConfig = SecureAggConfig(),
                      dp_cfg: dp_mod.DPConfig = dp_mod.DPConfig(),
                      key=None, cohort_order=None, stats=None):
    """Fused entry: consume a CohortEngine's already-stacked cohort output
    (leaves (n, ...)) directly — no unstack-to-host, no per-client dicts.
    Returns the cohort-mean update pytree.

    ``cohort_order``: the churn path — the FULL selected cohort in
    protocol (row) order, a superset of ``client_order`` (the survivors
    whose rows ``stacked_updates`` holds). Survivor rows scatter into
    their full-cohort positions (zeros at dropped rows, which the alive
    mask excludes), so each survivor keeps the DP key-fold of its
    selection-time row and the recovered mean is bit-identical to a clean
    round over the survivors."""
    template = jax.tree.map(lambda a: a[0], stacked_updates)
    _, unflatten = raveling.cached_unflatten(template)
    with tracing.span("secure_agg", n=len(client_order)) as sp:
        with tracing.span("ravel_rows") as rsp:
            rsp.transfer(h2d=stacked_updates)
            flat = ravel_rows(stacked_updates)
        alive = None
        if cohort_order is not None \
                and list(cohort_order) != list(client_order):
            with tracing.span("scatter_survivors", n=len(client_order)):
                cohort_order = list(cohort_order)
                pos_of = {cid: j for j, cid in enumerate(cohort_order)}
                positions = jnp.asarray([pos_of[c] for c in client_order],
                                        jnp.int32)
                full = jnp.zeros((len(cohort_order), flat.shape[1]),
                                 flat.dtype)
                flat = full.at[positions].set(flat)
                alive = np.zeros(len(cohort_order), bool)
                alive[np.asarray(positions)] = True
                client_order = cohort_order
        mean_flat = _aggregate_flat(sp, flat, plan, client_order,
                                    round_seed, secure_cfg, dp_cfg, key,
                                    None, alive, stats)
    return unflatten(mean_flat)


class PrivacyEngine:
    """Config-bound facade over the pipeline (the object the service layer
    and simulator thread through; jit caches are module-global, so engines
    are free to construct per round)."""

    def __init__(self, secure_cfg: SecureAggConfig = SecureAggConfig(),
                 dp_cfg: dp_mod.DPConfig = dp_mod.DPConfig()):
        self.secure_cfg = secure_cfg
        self.dp_cfg = dp_cfg

    def aggregate_flat(self, flat, plan, client_order, round_seed, key=None):
        return aggregate_flat(flat, plan, client_order, round_seed,
                              secure_cfg=self.secure_cfg,
                              dp_cfg=self.dp_cfg, key=key)

    def aggregate_stacked(self, stacked_updates, plan, client_order,
                          round_seed, key=None):
        return aggregate_stacked(stacked_updates, plan, client_order,
                                 round_seed, secure_cfg=self.secure_cfg,
                                 dp_cfg=self.dp_cfg, key=key)

    def aggregate_updates(self, updates, plan, round_seed, key=None):
        """Dict path convenience: {cid: update pytree} (sorted-cid row
        order, like the serial reference)."""
        cids = sorted(updates)
        flat, unflatten = stack_flat_updates([updates[c] for c in cids])
        return unflatten(self.aggregate_flat(flat, plan, cids, round_seed,
                                             key=key))
