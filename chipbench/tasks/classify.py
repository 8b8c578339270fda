"""Task kind ``classify``: the paper's §5.1 spam classifier, a two-class
head on the mean-pooled last hidden state of a causal decoder trunk,
trained with AdamW on every weight."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference as ref
from chipbench.traffic import round_rng, seed_words

SIGNAL_TOKENS = 64          # token ids below this mark spam
SIGNAL_RATE = 0.35          # share of a spam row's tokens drawn from them


class Traffic:
    """Synthetic enron-like spam (the generator of
    ``repro.data.synthetic.spam_dataset`` as ``benchmarks.common.SpamWorld``
    drives it, copied so that the yardstick does not move with the
    program). Each round a client picks one of ``n_splits`` splits at
    random and draws ``local_steps`` x ``batch`` distinct rows of it, so
    that no row of a client's round repeats."""

    def __init__(self, data: dict, model: dict, seed: int):
        self.p = dict(data)
        vocab = int(model["vocab_size"])
        n, s = int(data["n_train"]), int(data["seq_len"])
        w_data, w_split, self._word = seed_words(seed, 3, stream=1)
        rng = np.random.RandomState(w_data % 2**31)
        labels = rng.randint(0, 2, n).astype(np.int32)
        background = rng.zipf(1.5, size=(n, s)) % (vocab - SIGNAL_TOKENS) \
            + SIGNAL_TOKENS
        spam_block = rng.randint(1, SIGNAL_TOKENS, size=(n, s))
        use_signal = (rng.rand(n, s) < SIGNAL_RATE) & (labels[:, None] == 1)
        tokens = np.where(use_signal, spam_block, background).astype(np.int32)
        lengths = rng.randint(s // 2, s + 1, n)
        mask = (np.arange(s)[None, :] < lengths[:, None]).astype(np.float32)
        self.rows = {"tokens": tokens * mask.astype(np.int32),
                     "label": labels, "mask": mask}
        perm = np.random.RandomState(w_split % 2**31).permutation(n)
        self.splits = np.array_split(perm, int(data["n_splits"]))

    def batch_fn(self, cid, round_idx: int) -> dict:
        rng = round_rng(self._word, cid, round_idx)
        split = self.splits[rng.randint(len(self.splits))]
        shape = (int(self.p["local_steps"]), int(self.p["batch"]))
        idx = rng.choice(split, size=shape, replace=False)
        return {k: v[idx] for k, v in self.rows.items()}


def build(cfg, config: dict, workload: dict, key):
    from chipbench import cells
    from repro.core.cohort_engine import LocalTrainSpec
    from repro.models import classifier_init, classify_loss, init_params
    from repro.optim import adamw

    model, fl = config["model"], config["fl"]
    if fl["optimizer"] != "adamw":
        raise ValueError(f"classify runs adamw, not {fl['optimizer']!r}")
    shapes = jax.eval_shape(lambda k: {
        "trunk": init_params(cfg, k, max_positions=model["max_positions"]),
        "head": classifier_init(cfg, k)}, key)
    model0 = jax.jit(lambda k: cells.init_weights(shapes, k))(key)
    spec = LocalTrainSpec(
        loss_fn=lambda m, b: classify_loss(cfg, m["trunk"], m["head"], b),
        optimizer=adamw(lr=fl["lr"]),
        local_steps=workload["data"]["local_steps"])
    return spec, model0, None


# --------------------------------------------------------------------------
# reference
# --------------------------------------------------------------------------

def classify_loss(model, batch, *, heads: int):
    """Mean cross entropy of the mean-pooled classifier (the program's
    spam head over its causal decoder trunk)."""
    emb, trunk = model["trunk"]["embed"], model["trunk"]["trunk"]
    tokens = batch["tokens"]
    x = emb["tok"][tokens] + emb["pos"][:tokens.shape[-1]]
    blocks = trunk["blocks"][0]
    for i in range(blocks["attn"]["wq"].shape[0]):
        p = ref.layer(blocks, i)
        y = ref.ln(p["norm1"], x)
        x = x + ref.attn(p["attn"], y, y, heads, causal=True)
        x = x + ref.mlp(p["ffn"], ref.ln(p["norm2"], x))
    h = ref.ln(trunk["final_norm"], x)
    m = batch["mask"].astype(h.dtype)[..., None]
    pooled = jnp.sum(h * m, 1) / jnp.maximum(jnp.sum(m, 1), 1.0)
    logits = pooled @ model["head"]["w"] + model["head"]["b"]
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, batch["label"][:, None], -1)[:, 0]
    return jnp.mean(lse - gold)


def reference_client(recipe: dict, model: dict, dtype, frozen):
    """-> jitted f(model, batches) -> (delta f32, mean loss, first
    gradient f32): AdamW over the client's ``local_steps`` batches."""
    steps, lr = int(recipe["local_steps"]), float(recipe["lr"])
    heads = int(model["num_heads"])

    def client(params, batches):
        p0 = ref.cast(params, dtype)
        loss = lambda p, b: classify_loss(p, ref.cast(b, dtype), heads=heads)
        p, mean_loss, g0 = ref.adamw_steps(loss, p0, batches, lr, steps)
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), p, p0)
        return delta, mean_loss.astype(jnp.float32), ref.cast(g0, jnp.float32)
    return jax.jit(client)


# --------------------------------------------------------------------------
# model FLOPs
# --------------------------------------------------------------------------

def forward_flops(m: dict, seq: int) -> dict:
    """One sequence: ``num_layers`` self-attention layers over ``seq``
    positions and the two-class head."""
    per = flops.encoder_layer_forward(m["d_model"], m["d_ff"], seq)
    return {"matmul": m["num_layers"] * per["matmul"] + 2 * m["d_model"] * 2,
            "attention": m["num_layers"] * per["attention"]}


def train_flops(fwd: dict) -> int:
    """Full fine-tuning: forward plus backward is 3x the forward."""
    return 3 * (fwd["matmul"] + fwd["attention"])


def sample_flops(model: dict, data: dict) -> int:
    return train_flops(forward_flops(model, int(data["seq_len"])))
