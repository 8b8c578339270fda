"""Multi-host emulation lane: the production fl_round on an 8-device host.

The in-process suite runs on ONE device by design (conftest.py forbids
setting ``xla_force_host_platform_device_count`` globally — every other
test and bench must see the single-device world). This lane spawns a
fresh python with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
in the subprocess env only, builds a real (pod=4, data=2) mesh, and runs
the per_pod fl_round: the stage-2 combine must take the shard_map pod
route (per-pod limb states + one uint32 psum across 4 pods) and training
must still reduce loss. A second subprocess gives 4 devices to
``chip_smoke.py``'s four-chip phase. Marked ``multihost``; deselect with
``-m 'not multihost'`` when iterating.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import jax
assert jax.device_count() == 8, f"expected 8 emulated devices, got {jax.devices()}"
import jax.numpy as jnp
import numpy as np
from repro import compat
from repro.configs import get_reduced_config
from repro.launch.fl_step import make_fl_train_step
from repro.models import init_params
from repro.optim import adamw

cfg = get_reduced_config("deepseek-67b")
assert cfg.fl_scheme == "per_pod"
# 4 pods x 2-way data (FSDP inside each pod-silo); vg_size=1 keeps the VG
# axis divisible by the pod axis so stage 2 takes the shard_map route
mesh = compat.make_mesh((4, 2, 1), ("pod", "data", "model"))
with compat.set_mesh(mesh):
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw().init(params)
    step, meta = make_fl_train_step(cfg, mesh, secure=True, vg_size=1,
                                    microbatches=1, server_lr=5e-3)
    assert meta["stage2_pod_axis"] == "pod", meta
    assert meta["n_silos"] == 4, meta
    step = jax.jit(step)
    rng = np.random.RandomState(0)
    b, s = 4, 16
    batch = {
        "tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, b, s)),
                              jnp.int32),
        "targets": jnp.asarray(rng.randint(0, cfg.vocab_size, (4, b, s)),
                               jnp.int32),
        "mask": jnp.ones((4, b, s), jnp.float32),
    }
    losses = []
    for i in range(4):
        seed = jnp.asarray([i, i + 1], jnp.uint32)
        params, opt_state, loss = step(params, opt_state, batch, seed)
        losses.append(float(loss))
assert losses[-1] < losses[0], losses
print("MULTIHOST_OK", losses)
"""


_FOUR_CHIP_SCRIPT = r"""
import jax
assert jax.device_count() == 4, jax.devices()
import chip_smoke
out = chip_smoke.four_chip_phase(jax.devices())
assert out["stage2_bit_identical"], out
print("FOUR_CHIP_OK", out["losses"])
"""


def _run_emulated(script: str, n_devices: int) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{n_devices}",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(REPO, "src"),
                                           REPO]))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    return proc.stdout


@pytest.mark.multihost
def test_per_pod_round_on_emulated_8_device_host():
    assert "MULTIHOST_OK" in _run_emulated(_SCRIPT, 8)


@pytest.mark.multihost
def test_chip_smoke_four_chip_phase_on_emulated_4_device_host():
    """``chip_smoke.py --four-chips``'s phase on 4 emulated devices: the
    shard_map stage-2 route, falling loss, stage-2 bit-identity with the
    zero-padded route, one pod shard per device."""
    assert "FOUR_CHIP_OK" in _run_emulated(_FOUR_CHIP_SCRIPT, 4)
