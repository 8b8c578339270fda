"""Plain reference of the federated round, for the check that decides
``correct``.

It imports nothing of the program. It reads the weights that the benchmark
made from the seed (``cells.init_weights``) and the client data of the
cell's traffic, and follows the program's own equations as run:

- the model: a pre-LayerNorm transformer (LayerNorm eps 1e-6, tanh GELU,
  learned or sinusoidal positions as the program has them), in straight
  ``jax.numpy``: the layers here, each task kind's model and local
  training in its ``tasks/<kind>.py`` (``reference_client``);
- local training: the client's loss is the mean of its steps' losses;
- the privacy chain, by what it guarantees: pairwise masks cancel exactly
  in the virtual-group sums (a dropped client's masks are recovered), so
  the aggregate is the mean of each submitting client's 20-bit code of its
  (local-DP) update; the DP noise is the documented draw
  ``N(0, sigma^2)`` from ``fold_in(PRNGKey(round), row)``, ``row`` being
  the client's place in the sorted selected cohort;
- the server update: FedAvg with server learning rate 1.

``dtype`` float32 runs at ``highest`` matmul precision (the reference);
bfloat16 is the control, the same code one precision below what the
configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

NEG = -1e30


def ln(p, x, eps=1e-6):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def mlp(p, x):
    return gelu(x @ p["w_in"] + p["b_in"]) @ p["w_out"] + p["b_out"]


def attn(p, x, kv, heads: int, causal: bool):
    b, sq, d = x.shape
    sk = kv.shape[1]
    hd = p["wq"].shape[-1] // heads
    q = (x @ p["wq"] + p["bq"]).reshape(b, sq, heads, hd)
    k = (kv @ p["wk"] + p["bk"]).reshape(b, sk, heads, hd)
    v = (kv @ p["wv"] + p["bv"]).reshape(b, sk, heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (hd ** -0.5)
    if causal:
        keep = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(keep, s, jnp.asarray(NEG, s.dtype))
    w = jnp.exp(s - jnp.max(s, -1, keepdims=True))
    w = w / jnp.sum(w, -1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, sq, heads * hd)
    return o @ p["wo"] + p["bo"]


def layer(tree, i):
    return jax.tree.map(lambda a: a[i], tree)


def sinusoid(s, d, dtype):
    pos = jnp.arange(s, dtype=jnp.float32)[:, None]
    ang = pos / jnp.power(10_000.0, jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)[:, :d].astype(dtype)


def cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, tree)


def adamw_steps(loss, params, batches, lr, steps, b1=0.9, b2=0.999,
                 eps=1e-8):
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    p, losses, g0 = params, [], None
    for t in range(1, steps + 1):
        batch = jax.tree.map(lambda a: a[t - 1], batches)
        val, g = jax.value_and_grad(loss)(p, batch)
        g0 = g if g0 is None else g0
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * (m_ / (1 - b1 ** t))
            / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps), p, m, v)
        losses.append(val)
    return p, jnp.mean(jnp.stack(losses)), g0


# --------------------------------------------------------------------------
# the round
# --------------------------------------------------------------------------

def quantize_codes(flat, clip: float, bits: int):
    """Each coordinate's ``bits``-bit code (int32, on the device)."""
    levels = np.float32((1 << bits) - 1)
    x = jnp.clip(flat.astype(jnp.float32), -clip, clip)
    return jnp.round((x + clip) / (2.0 * clip) * levels).astype(jnp.int32)


def secure_mean(deltas: list, rows: list, round_idx: int, dp: dict,
                clip: float, bits: int):
    """Mean of the submitting clients' 20-bit codes; ``rows[i]`` is client
    ``i``'s place in the sorted selected cohort (the DP key fold)."""
    if len(deltas) << bits >= 2**31:
        raise ValueError(f"{len(deltas)} codes of {bits} bits overflow int32")
    total, unravel = None, None
    for row, d in zip(rows, deltas):
        flat, unravel = ravel_pytree(d)
        flat = flat.astype(jnp.float32)
        if dp.get("mechanism", "off") in ("local", "global"):
            norm = jnp.sqrt(jnp.sum(jnp.square(flat)))
            flat = flat * jnp.minimum(1.0, dp["clip_norm"]
                                      / jnp.maximum(norm, 1e-12))
        if dp.get("mechanism") == "local" and dp["noise_multiplier"] > 0:
            sigma = float(dp["noise_multiplier"] * dp["clip_norm"])
            key = jax.random.fold_in(jax.random.PRNGKey(round_idx), row)
            flat = flat + sigma * jax.random.normal(key, flat.shape,
                                                    jnp.float32)
        codes = quantize_codes(flat, clip, bits)
        total = codes if total is None else total + codes
    levels = float((1 << bits) - 1)
    total = np.asarray(total, np.float64)
    mean = (total / len(deltas)) / levels * (2.0 * clip) - clip
    return unravel(jnp.asarray(mean, jnp.float32))


def run_rounds(kind: str, state0, traffic, cohorts: list, recipe: dict,
               model: dict, *, dtype=jnp.float32, frozen=None,
               half_batch: bool = False):
    """The reference's own trajectory over ``len(cohorts)`` rounds from
    ``state0`` (the trainable tree: model or adapters); ``cohorts`` holds
    each round's ``(selected cohort, survivors)``: the survivors train and
    submit, and the mean is over them. Returns
    ``(losses per round (list of per-survivor arrays, survivor order),
    [state after each round], [per-leaf norm of the clients' mean first
    gradient, each round])``. ``half_batch`` leaves out half of each
    client's batch (a planted fault, for calibration)."""
    from chipbench import tasks
    chain = recipe["chain"]
    prec = "highest" if dtype == jnp.float32 else "default"
    run_client = tasks.load(kind).reference_client(recipe, model, dtype,
                                                   frozen)
    state = jax.tree.map(jnp.asarray, state0)
    losses, states, grad_norms = [], [], []
    with jax.default_matmul_precision(prec):
        for r, (cohort, survivors) in enumerate(cohorts):
            by_cid = {}
            for cid in survivors:
                b = traffic.batch_fn(cid, r)
                if half_batch:
                    b = {k: v[:, :max(1, v.shape[1] // 2)] for k, v in b.items()}
                by_cid[cid] = run_client(state, b)
            order = sorted(survivors)
            row_of = {c: j for j, c in enumerate(sorted(cohort))}
            gsum = jax.tree.map(lambda *g: sum(g[1:], g[0]),
                                *[by_cid[c][2] for c in order])
            grad_norms.append(np.asarray(
                [float(jnp.linalg.norm(g)) / len(order)
                 for g in jax.tree.leaves(gsum)]))
            mean = secure_mean([by_cid[c][0] for c in order],
                               [row_of[c] for c in order], r,
                               recipe.get("dp", {}), float(chain["clip"]),
                               int(chain["bits"]))
            state = jax.tree.map(lambda p, d: (p.astype(jnp.float32) + d),
                                 state, mean)
            losses.append(np.asarray([float(by_cid[c][1])
                                      for c in survivors]))
            states.append(jax.tree.map(np.asarray, state))
    return losses, states, grad_norms
