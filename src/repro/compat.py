"""Mesh helpers shared by the launchers, the models and the tests.

:func:`make_mesh` pins every axis to ``AxisType.Auto`` (the sharding
rules in ``launch/shardings.py`` assume automatic propagation), and
:func:`get_abstract_mesh` maps "no mesh set" to ``None``.
"""
from __future__ import annotations


def make_mesh(shape, axis_names, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``; ``devices``
    restricts the mesh to those devices."""
    import jax

    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(shape, axis_names, axis_types=axis_types,
                         devices=devices)


def get_abstract_mesh():
    """The mesh set by ``jax.set_mesh``, else ``None`` (JAX itself returns
    an *empty* ``AbstractMesh`` when none is set)."""
    import jax

    m = jax.sharding.get_abstract_mesh()
    return m if m is not None and m.axis_names else None
