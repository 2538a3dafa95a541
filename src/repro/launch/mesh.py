"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — required because
smoke tests must see 1 CPU device while the dry-run forces 512.

The triples-mode bridge: ``mesh_from_triples`` maps the paper's
(nodes, NPPN, threads) launch triple onto mesh axes —
nodes -> pod axis, NPPN -> data axis, threads x chips -> model axis.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core.triples import TriplesConfig

# TPU v5e hardware constants (roofline denominators).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link

V5E_HBM_BYTES = 16e9            # per chip

SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return jax.make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> jax.sharding.Mesh:
    """Arbitrary mesh (elastic re-mesh after worker loss uses this)."""
    return jax.make_mesh(shape, axes)


def mesh_from_triples(cfg: TriplesConfig, chips_per_node: int = 4,
                      pods: int = 1) -> jax.sharding.Mesh:
    """Map a triples-mode request onto a device mesh.

    nodes x nppn x (threads x chips) must equal the available device
    count; the same exclusive-mode arithmetic from core/triples.py
    validates the request before any devices are touched.
    """
    n_devices = len(jax.devices())
    shape = cfg.mesh_shape(chips_per_node)
    total = int(np.prod(shape)) * pods
    if total != n_devices:
        raise ValueError(
            f"triples {shape} x {pods} pods = {total} devices, "
            f"but {n_devices} are available")
    if pods > 1:
        return jax.make_mesh((pods, *shape[:2], shape[2]),
                             ("pod", "nodes", "data", "model"))
    return jax.make_mesh(shape, ("nodes", "data", "model"))
