"""Thin wrappers over JAX's mesh APIs, written for JAX 0.9.

The call sites (models/model.py, models/attention.py, models/moe.py,
launch/fl_step.py, launch/mesh.py, core/cohort_engine.py) go through these
functions so each mesh question has one answer in one place.

``get_abstract_mesh`` is the load-bearing one: the §Perf
with-sharding-constraint helpers ask "is a mesh ambient, and which axes
does it have?" before pinning activation layouts. JAX answers with an
``AbstractMesh`` whose ``axis_names`` is empty when no mesh is set; this
module normalizes that to *None when unmeshed*, so call sites stay a plain
``if mesh is None: return x`` no-op on CPU smoke tests.
"""
from __future__ import annotations

import jax


def get_abstract_mesh():
    """Return the ambient abstract mesh (set by :func:`set_mesh`), or None
    if unmeshed. The returned object has ``axis_names`` and ``axis_sizes``;
    use :func:`mesh_axis_sizes` for a name->size dict."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def mesh_axis_sizes(mesh) -> dict:
    """{axis_name: size} for any mesh object returned above."""
    if mesh is None:
        return {}
    return dict(zip(mesh.axis_names, mesh.axis_sizes))


def set_mesh(mesh):
    """Context manager making ``mesh`` ambient (``jax.set_mesh``);
    :func:`get_abstract_mesh` sees it inside the block."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check_rep=False):
    """``jax.shard_map`` with the replication checker (``check_vma``) off
    by default: the FL combine paths feed uint32 collectives (psum of limb
    states) whose replication rule the checker rejects."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_rep)


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (GSPMD decides
    layouts the sharding constraints leave open)."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kw)
