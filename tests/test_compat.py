"""Mesh wrappers in ``repro.compat``: ambient-mesh introspection normalizes
JAX's "no mesh" answers to None, and the §Perf with-sharding-constraint
helpers are exact no-ops on unmeshed CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import get_config
from repro.launch.fl_step import _mb_constraint
from repro.launch.mesh import make_host_mesh
from repro.models.attention import _shard_heads
from repro.models.model import _constrain_batch_axis
from repro.models.moe import _constrain


class _FakeMesh:
    def __init__(self, names=("data",), sizes=(1,)):
        self.axis_names = names
        self.axis_sizes = sizes


def test_unmeshed_returns_none():
    assert compat.get_abstract_mesh() is None
    assert compat.mesh_axis_sizes(None) == {}


def test_mesh_context_visible():
    mesh = make_host_mesh()
    with compat.set_mesh(mesh):
        got = compat.get_abstract_mesh()
        assert got is not None
        assert set(got.axis_names) >= {"data", "model"}
        sizes = compat.mesh_axis_sizes(got)
        assert sizes["data"] * sizes["model"] == len(jax.devices())
    assert compat.get_abstract_mesh() is None


def test_new_api_preferred_when_present(monkeypatch):
    fake = _FakeMesh(("pod", "data"), (2, 8))
    monkeypatch.setattr(jax.sharding, "get_abstract_mesh", lambda: fake,
                        raising=False)
    assert compat.get_abstract_mesh() is fake
    assert compat.mesh_axis_sizes(fake) == {"pod": 2, "data": 8}


def test_new_api_empty_sentinel_falls_through(monkeypatch):
    # JAX's AbstractMesh() "no mesh" sentinel has no axes -> unmeshed.
    monkeypatch.setattr(jax.sharding, "get_abstract_mesh",
                        lambda: _FakeMesh((), ()), raising=False)
    assert compat.get_abstract_mesh() is None


@pytest.mark.parametrize("api", ["new_none", "new_empty"])
def test_constraint_helpers_noop_unmeshed(monkeypatch, api):
    """model/attention/moe/fl_step mesh-constraint helpers: identity on
    unmeshed CPU whichever "no mesh" answer JAX gives."""
    if api == "new_none":
        monkeypatch.setattr(jax.sharding, "get_abstract_mesh", lambda: None,
                            raising=False)
    elif api == "new_empty":
        monkeypatch.setattr(jax.sharding, "get_abstract_mesh",
                            lambda: _FakeMesh((), ()), raising=False)

    cfg = get_config("bert-tiny-spam").replace(
        activation_batch_axes=("data",), shard_attn_heads=True,
        moe_dispatch_constraint=True)

    x = jnp.ones((4, 8, 16))
    np.testing.assert_array_equal(np.asarray(_constrain_batch_axis(cfg, x)),
                                  np.asarray(x))
    t = jnp.ones((2, 8, 4, 8))
    np.testing.assert_array_equal(np.asarray(_shard_heads(cfg, t)),
                                  np.asarray(t))
    np.testing.assert_array_equal(
        np.asarray(_constrain(cfg, x, (None, "model", None))), np.asarray(x))
    cfg_pod = cfg.replace(fl_scheme="per_pod")
    f = _mb_constraint(cfg_pod)
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x))


def test_make_mesh_axes_are_auto():
    mesh = compat.make_mesh((len(jax.devices()),), ("data",))
    assert mesh.shape["data"] == len(jax.devices())
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)


def test_shard_map_psum_uint32_over_mesh_axis():
    """compat.shard_map runs a uint32 psum (the stage-2 limb merge's
    collective) with the replication checker off."""
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((len(jax.devices()),), ("pod",))
    n = len(jax.devices())
    x = jnp.arange(4 * n, dtype=jnp.uint32).reshape(n, 4)
    f = compat.shard_map(lambda a: jax.lax.psum(a, "pod"), mesh=mesh,
                         in_specs=P("pod"), out_specs=P())
    np.testing.assert_array_equal(np.asarray(f(x)),
                                  np.asarray(x).sum(0, keepdims=True))
