"""``chip_smoke.py``'s phases at toy size on the CPU: the same control flow
and checks the chip run takes (kernel path == jnp path bit for bit, finite
losses, the model moves), without the chip. ``main()`` itself must refuse
any backend that is not a TPU."""
import numpy as np
import pytest

import chip_smoke
from repro.configs import get_reduced_config


def test_paper_phase_toy_kernel_path_bit_identical():
    out = chip_smoke.paper_phase(
        n_clients=10, clients_per_round=4, batch_size=2, n_rounds=2,
        local_steps=1, vg_size=2, seq_len=8, n_train=500,
        vocab=256, d_model=32, d_ff=64, head_dim=16)
    assert out["bit_identical"]
    assert len(out["losses"]) == 2 and np.all(np.isfinite(out["losses"]))
    # the CPU backend interprets the kernel: no Mosaic call in the program
    assert out["tpu_custom_call"] is False


def test_whisper_phase_toy_lora_round():
    out = chip_smoke.whisper_phase(
        cfg=get_reduced_config("whisper-medium"), n_clients=3,
        enc_frames=16, dec_tokens=8)
    assert out["adapters_moved"]
    assert len(out["losses"]) == 3 and np.all(np.isfinite(out["losses"]))


def test_cohort_interims_args_match_the_round_plan():
    args, statics = chip_smoke.cohort_interims_args(20, 1000, 8)
    # 20 clients at VG size 8: groups of 8, 8 and a remainder of 4
    assert statics["bucket_shapes"] == ((4, 1), (8, 2))
    assert statics["n_shards"] == 1
    assert args[0].shape == (20, 1000)
    assert [a.shape for a in args[3]] == [(4,), (16,)]


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_a_backend_without_tpu(argv, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out
