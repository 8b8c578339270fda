"""The numbers that decide ``correct``, each against its limit.

The program's first three rounds (run in set-up, through the window's own
call) are compared with the reference's own three rounds from the same
seed:

- ``median_loss_gap``: the median over the clients of the relative gap of
  a client's loss in the first round, where every client starts from the
  model the reference starts from. It catches losses that are wrong or
  given to the wrong client across the cohort. Not the widest gap: a few
  clients of a cohort are ill-conditioned (their five AdamW steps part
  by 10-30% on round-off alone, at any precision: the bfloat16 control
  parts on the same clients), so the widest reads as high on sound runs
  as on broken ones;
- ``mean_loss_gap``: the widest relative gap of the cohort's mean loss
  (what the server records as the round's loss), over the three rounds;
- ``update_gap``: the first aggregated update as the server applies it
  (model after round 1 minus model at round 0), by the worst leaf;
- ``change_gap``: the change of the model over the three rounds, by the
  worst leaf.

A leaf's gap is ``| |prog| - |ref| |`` over the larger of ``|ref|`` for
that leaf and the median leaf's ``|ref|`` (norms, not the norm of the
difference). A leaf is left out of a comparison where the reference's
gradient of it (the clients' mean first gradient) is under a thousandth of
the median leaf's in every round the comparison covers: it moves by
round-off alone, which Adam scales up to a full step. That is a key's bias
under softmax, and a LoRA ``A`` factor in the first round, while ``B`` is
still zero.
"""
from __future__ import annotations

import jax
import numpy as np

SKIP_BELOW = 1e-3


def moving_leaves(grad_norms: list) -> np.ndarray:
    """Leaves whose gradient reaches a thousandth of the median leaf's in
    at least one of the rounds given."""
    keep = np.zeros(len(grad_norms[0]), bool)
    for g in grad_norms:
        keep |= g >= SKIP_BELOW * np.median(g)
    return keep


def leaf_gap(prog_change, ref_change, keep) -> float:
    p = [float(np.linalg.norm(np.asarray(a, np.float64)))
         for a in jax.tree.leaves(prog_change)]
    r = [float(np.linalg.norm(np.asarray(a, np.float64)))
         for a in jax.tree.leaves(ref_change)]
    if len(p) != len(r):
        raise ValueError(f"{len(p)} program leaves against {len(r)}")
    med = float(np.median(r))
    gaps = [abs(pn - rn) / max(rn, med) for pn, rn, k in zip(p, r, keep)
            if k and max(rn, med) > 0]
    return max(gaps) if gaps else float("inf")


def loss_gap(prog_losses: list, ref_losses: list) -> float:
    """Widest relative gap of a client's loss over the rounds given."""
    worst = 0.0
    for p, r in zip(prog_losses, ref_losses):
        p, r = np.asarray(p, np.float64), np.asarray(r, np.float64)
        if p.shape != r.shape or not np.all(np.isfinite(p)):
            return float("inf")
        worst = max(worst, float(np.max(np.abs(p - r) / np.abs(r))))
    return worst


def median_loss_gap(prog_losses, ref_losses) -> float:
    """Median over the clients of the relative gap of a client's loss."""
    p, r = np.asarray(prog_losses, np.float64), np.asarray(ref_losses,
                                                          np.float64)
    if p.shape != r.shape or not np.all(np.isfinite(p)):
        return float("inf")
    return float(np.median(np.abs(p - r) / np.abs(r)))


def mean_loss_gap(prog_losses: list, ref_losses: list) -> float:
    """Widest relative gap of the cohort's mean loss over the rounds."""
    return loss_gap([[np.mean(p)] for p in prog_losses],
                    [[np.mean(r)] for r in ref_losses])


def _sub(a, b):
    return jax.tree.map(lambda x, y: np.asarray(x, np.float64)
                        - np.asarray(y, np.float64), a, b)


def numbers(state0, prog_losses, prog_states, ref_losses, ref_states,
            grad_norms) -> dict:
    """``prog_states``/``ref_states``: the trainable tree after each of the
    three rounds; ``grad_norms``: the reference's per-leaf gradient norms,
    each round."""
    return {
        "median_loss_gap": median_loss_gap(prog_losses[0], ref_losses[0]),
        "mean_loss_gap": mean_loss_gap(prog_losses, ref_losses),
        "update_gap": leaf_gap(_sub(prog_states[0], state0),
                               _sub(ref_states[0], state0),
                               moving_leaves(grad_norms[:1])),
        "change_gap": leaf_gap(_sub(prog_states[2], state0),
                               _sub(ref_states[2], state0),
                               moving_leaves(grad_norms[:3])),
    }


def judge(values: dict, limits: dict) -> tuple:
    """-> (correct, {name: {"value", "limit"}}); a number with no limit, or
    one that is not finite, fails."""
    checks = {k: {"value": v, "limit": limits.get(k)} for k, v in values.items()}
    ok = all(c["limit"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
