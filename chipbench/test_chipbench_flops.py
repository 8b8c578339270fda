"""FLOP and byte counters against counts made by hand, and the peaks
table."""
import pytest

from chipbench import cells, flops, tasks

D, FF, V = 1024, 4096, 51865            # whisper-medium
ENC, DEC, LAYERS = 1500, 448, 24


def mm(m, k, n):
    """FLOPs of an (m, k) @ (k, n) product."""
    return 2 * m * k * n


def test_whisper_medium_forward_per_utterance():
    enc_layer = (4 * mm(ENC, D, D)                     # q, k, v, o
                 + mm(ENC, D, FF) + mm(ENC, FF, D)     # ffn
                 + mm(ENC, D, ENC) + mm(ENC, ENC, D))  # scores, weighted sum
    dec_layer = (4 * mm(DEC, D, D)                     # self q, k, v, o
                 + 2 * mm(DEC, D, D)                   # cross q, o
                 + 2 * mm(ENC, D, D)                   # cross k, v (frames)
                 + mm(DEC, D, FF) + mm(DEC, FF, D)
                 + mm(DEC, D, DEC) + mm(DEC, DEC, D)   # self attention
                 + mm(DEC, D, ENC) + mm(DEC, ENC, D))  # cross attention
    unembed = mm(DEC, D, V)
    hand = LAYERS * enc_layer + LAYERS * dec_layer + unembed
    assert hand == 1_727_205_343_232
    m = cells.load_json("configs", "whisper-medium")["model"]
    fwd = tasks.load("lora_lm").forward_flops(m, ENC, DEC)
    assert fwd["matmul"] + fwd["attention"] == hand
    attention = LAYERS * (mm(ENC, D, ENC) + mm(ENC, ENC, D)
                          + mm(DEC, D, DEC) + mm(DEC, DEC, D)
                          + mm(DEC, D, ENC) + mm(DEC, ENC, D))
    assert fwd["attention"] == attention
    # LoRA: frozen matmuls forward + activation gradient, attention 3x
    assert tasks.load("lora_lm").train_flops(fwd) == \
        2 * (hand - attention) + 3 * attention


def test_bert_tiny_per_token():
    d, ff, s = 128, 512, 128
    per_token_layer = (4 * mm(1, d, d) + mm(1, d, ff) + mm(1, ff, d)
                       + mm(1, d, s) + mm(1, s, d))
    assert per_token_layer == 458_752
    m = cells.load_json("configs", "bert-tiny-spam")["model"]
    fwd = tasks.load("classify").forward_flops(m, s)
    head = mm(1, d, 2)
    assert fwd["matmul"] + fwd["attention"] == 2 * s * per_token_layer + head
    assert tasks.load("classify").train_flops(fwd) == \
        3 * (2 * s * per_token_layer + head)


def test_round_flops_scale_with_the_cohort():
    conf = cells.load_json("configs", "whisper-medium")
    w = cells.load_json("workloads", "whisper-lora")
    lora_lm = tasks.load("lora_lm")
    per = lora_lm.train_flops(lora_lm.forward_flops(conf["model"], ENC, DEC))
    assert flops.round_model_flops(conf, w) == 8 * 1 * 2 * per


def test_chain_bytes():
    # read 20 x size f32 updates once, write one 3-limb uint32 state
    assert flops.chain_bytes(20, 1000) == 4 * 20 * 1000 + 4 * 3 * 1000


def test_peaks_table():
    v5e = flops.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("cpu")
