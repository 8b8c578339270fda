"""Task kind ``lora_lm``: an encoder-decoder (Whisper's shape) whose frozen
base is an argument of every cohort call, trained through LoRA adapters
on the attention projections with SGD."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, reference as ref
from chipbench.traffic import round_rng, seed_words

FRAME_SCALE = 0.02          # stub frame embeddings are N(0, FRAME_SCALE^2)


class Traffic:
    """Utterances of ``enc_frames`` stub frame embeddings (the conv front
    end is stubbed, as in the program) and ``dec_tokens`` decoder tokens.
    The frames come from a pool of ``frame_pool`` made at set-up; the
    tokens and targets are drawn per client and round, so every utterance
    differs."""

    def __init__(self, data: dict, model: dict, seed: int):
        self.p = dict(data)
        self.vocab = int(model["vocab_size"])
        w_frames, self._word = seed_words(seed, 2, stream=1)
        rng = np.random.default_rng(w_frames)
        self.frames = rng.standard_normal(
            (int(data["frame_pool"]), int(data["enc_frames"]),
             int(model["d_model"])), dtype=np.float32) \
            * np.float32(FRAME_SCALE)

    def batch_fn(self, cid, round_idx: int) -> dict:
        rng = round_rng(self._word, cid, round_idx)
        steps, b = int(self.p["local_steps"]), int(self.p["batch"])
        tok = (steps, b, int(self.p["dec_tokens"]))
        pick = rng.randint(0, self.frames.shape[0], size=(steps, b))
        return {"frames": self.frames[pick],
                "tokens": rng.randint(0, self.vocab, tok).astype(np.int32),
                "targets": rng.randint(0, self.vocab, tok).astype(np.int32),
                "mask": np.ones(tok, np.float32)}


def build(cfg, config: dict, workload: dict, key):
    from chipbench import cells
    from repro.core import lora
    from repro.models import init_params, loss_fn
    from repro.optim import sgd

    fl = config["fl"]
    if fl["optimizer"] != "sgd":
        raise ValueError(f"lora_lm runs sgd, not {fl['optimizer']!r}")
    lc = lora.LoRAConfig(rank=fl["lora"]["rank"], alpha=fl["lora"]["alpha"],
                         include=tuple(fl["lora"]["include"]))
    base_shapes = jax.eval_shape(lambda k: init_params(cfg, k), key)
    by_path = {lora._path_str(p): s for p, s in
               jax.tree_util.tree_flatten_with_path(base_shapes)[0]}
    r = lc.rank
    ad_shapes = {
        p: {"A": jax.ShapeDtypeStruct((*by_path[p].shape[:-1], r),
                                      jnp.float32),
            "B": jax.ShapeDtypeStruct((*by_path[p].shape[:-2], r,
                                       by_path[p].shape[-1]), jnp.float32)}
        for p in lora.target_paths(lc, base_shapes)}
    base, adapters = jax.jit(
        lambda k: cells.init_weights((base_shapes, ad_shapes), k))(key)
    spec = lora.lora_spec(lc, base, lambda p, b: loss_fn(cfg, p, b),
                          sgd(fl["lr"]),
                          local_steps=workload["data"]["local_steps"])
    return spec, adapters, base


def toy(config: dict, workload: dict) -> tuple:
    """The CPU tests' shrink of a ``lora_lm`` cell, in place: one encoder
    and one decoder layer at toy widths, 32 frames and 16 tokens, 16
    clients and 4 a round. Returns ``(config, workload)``."""
    config["model"].update(num_layers=1, num_encoder_layers=1, d_model=64,
                           num_heads=2, num_kv_heads=2, head_dim=32,
                           d_ff=128, vocab_size=512)
    workload["data"].update(enc_frames=32, dec_tokens=16, frame_pool=8)
    workload["task"].update(clients=16, clients_per_round=4)
    return config, workload


# --------------------------------------------------------------------------
# reference
# --------------------------------------------------------------------------

def merge_lora(base, adapters, scale: float):
    """W + scale * A @ B at each adapter path ("trunk/encoder/attn/wq")."""
    def leaf(path, w):
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                       for k in path)
        ab = adapters.get(key)
        return w if ab is None else w + scale * (ab["A"] @ ab["B"])
    return jax.tree_util.tree_map_with_path(leaf, base)


def seq2seq_nll(params, utt, *, heads: int):
    """Summed masked NLL of one utterance of the encoder-decoder (tied
    unembedding), and its mask count. Layers are rematerialized so that a
    1,500-frame utterance fits; that changes no arithmetic."""
    trunk = params["trunk"]
    frames = utt["frames"][None]
    x = frames + ref.sinusoid(frames.shape[1], frames.shape[2], frames.dtype)

    @jax.checkpoint
    def enc(x, p):
        y = ref.ln(p["norm1"], x)
        x = x + ref.attn(p["attn"], y, y, heads, causal=False)
        return x + ref.mlp(p["ffn"], ref.ln(p["norm2"], x)), None

    x, _ = jax.lax.scan(enc, x, trunk["encoder"])
    enc_out = ref.ln(trunk["enc_norm"], x)
    tok = params["embed"]["tok"]
    tokens = utt["tokens"][None]
    y = tok[tokens] + params["embed"]["pos"][:tokens.shape[-1]]

    @jax.checkpoint
    def dec(y, p):
        z = ref.ln(p["norm1"], y)
        y = y + ref.attn(p["self_attn"], z, z, heads, causal=True)
        y = y + ref.attn(p["cross_attn"], ref.ln(p["norm_x"], y), enc_out,
                         heads, causal=False)
        return y + ref.mlp(p["ffn"], ref.ln(p["norm2"], y)), None

    y, _ = jax.lax.scan(dec, y, trunk["decoder"])
    h = ref.ln(trunk["final_norm"], y)[0]
    logits = (h @ tok.T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, utt["targets"][:, None], -1)[:, 0]
    m = utt["mask"].astype(jnp.float32)
    return jnp.sum((lse - gold) * m), jnp.sum(m)


def reference_client(recipe: dict, model: dict, dtype, frozen):
    """-> f(adapters, batches) -> (delta f32, mean loss, first gradient
    f32): SGD over ``local_steps`` batches of utterances, one utterance per
    compiled call (the loss of a batch is its summed NLL over its summed
    mask, as in the program's chunked loss)."""
    lr, heads = float(recipe["lr"]), int(model["num_heads"])
    scale = float(recipe["lora"]["alpha"]) / float(recipe["lora"]["rank"])
    base = ref.cast(frozen, dtype)

    @jax.jit
    def utt_grad(adapters, base, utt):
        def f(a):
            return seq2seq_nll(merge_lora(base, a, scale),
                               ref.cast(utt, dtype), heads=heads)
        (nll, count), g = jax.value_and_grad(f, has_aux=True)(adapters)
        return nll, count, g

    def client(adapters, batches):
        a = ref.cast(adapters, dtype)
        losses, g0 = [], None
        for t in range(int(recipe["local_steps"])):
            nll, count, grad = 0.0, 0.0, None
            for u in range(jax.tree.leaves(batches)[0].shape[1]):
                utt = jax.tree.map(lambda x: jnp.asarray(x[t, u]), batches)
                n_u, c_u, g_u = utt_grad(a, base, utt)
                nll, count = nll + n_u.astype(jnp.float32), count + c_u
                grad = g_u if grad is None else jax.tree.map(jnp.add, grad,
                                                             g_u)
            denom = jnp.maximum(count, 1.0)
            if g0 is None:
                g0 = jax.tree.map(lambda g: g.astype(jnp.float32) / denom,
                                  grad)
            a = jax.tree.map(lambda p, g: p - (lr / denom).astype(p.dtype) * g,
                             a, grad)
            losses.append(nll / denom)
        delta = jax.tree.map(lambda x, y: x.astype(jnp.float32)
                             - y.astype(jnp.float32), a,
                             ref.cast(adapters, dtype))
        return delta, jnp.mean(jnp.stack(losses)), g0
    return client


# --------------------------------------------------------------------------
# model FLOPs
# --------------------------------------------------------------------------

def forward_flops(m: dict, enc: int, dec: int) -> dict:
    """One utterance: ``enc`` frames, ``dec`` tokens, a tied unembedding
    over ``vocab_size``."""
    d, f, v = m["d_model"], m["d_ff"], m["vocab_size"]
    e = flops.encoder_layer_forward(d, f, enc)
    dec_matmul = (dec * (2 * 4 * d * d + 2 * 2 * d * d + 2 * 2 * d * f)
                  + enc * 2 * 2 * d * d)        # cross K, V over the frames
    dec_attn = 4 * dec * dec * d + 4 * dec * enc * d
    return {"matmul": m["num_encoder_layers"] * e["matmul"]
            + m["num_layers"] * dec_matmul + 2 * dec * d * v,
            "attention": m["num_encoder_layers"] * e["attention"]
            + m["num_layers"] * dec_attn}


def train_flops(fwd: dict) -> int:
    """The frozen base gets no weight gradient, so its matrices cost the
    forward and the activation gradient (2x); attention's scores still
    need gradients for both of their operands (3x). The adapters' own
    products are left out (rank 8 against width 1,024: under 2%)."""
    return 2 * fwd["matmul"] + 3 * fwd["attention"]


def sample_flops(model: dict, data: dict) -> int:
    return train_flops(forward_flops(model, int(data["enc_frames"]),
                                     int(data["dec_tokens"])))
