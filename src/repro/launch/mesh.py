"""Device meshes and sharding-spec helpers.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.

FL semantics (DESIGN.md §2): the ("pod","data") shards ARE the paper's clients;
sample-based q-aggregation is the all-reduce over those axes. The "model" axis
carries tensor/expert parallelism (and the feature-based ω_i blocks).
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def make_client_mesh(num_devices: int | None = None, axis: str = "data"):
    """Small 1-D client mesh for the sharded sample-based topology
    (core/topology.py): `axis` carries the paper's clients, client i lives on
    device i mod D. Defaults to ALL host devices, so CI can exercise the
    collective path with ``--xla_force_host_platform_device_count=8`` and a
    laptop gets a 1-device mesh (psum over a size-1 axis — the degenerate
    sharded topology every tier-1 run covers)."""
    import numpy as np
    devices = jax.devices()
    n = num_devices or len(devices)
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices for the client mesh, have "
                           f"{len(devices)}; set XLA_FLAGS="
                           f"--xla_force_host_platform_device_count={n}")
    return jax.sharding.Mesh(np.asarray(devices[:n]), (axis,))


def make_feature_mesh(num_devices: int | None = None):
    """1-D "model"-axis mesh for the sharded feature-based topology
    (core/topology.py feature_sum, DESIGN.md §12): each model-axis shard IS
    a vertical-FL feature client holding its ω_i block and feature slice.
    Same device policy as `make_client_mesh`."""
    return make_client_mesh(num_devices, axis="model")


def data_axes(mesh) -> tuple:
    """The axes a global-batch dimension shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def adapt_for_mesh(spec_tree, mesh):
    """Rewrites activation/cache PartitionSpecs written against the single-pod
    axis names: any 'data' entry becomes ('pod','data') on a multi-pod mesh.
    Param specs are NOT adapted — FSDP stays within a pod (DCN-frugal)."""
    if "pod" not in mesh.axis_names:
        return spec_tree
    def fix(spec):
        if not isinstance(spec, P):
            return spec
        return P(*(("pod", "data") if e == "data" else e for e in spec))
    return jax.tree.map(fix, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def fit_specs(spec_tree, shape_tree, mesh):
    """Shape-aware spec repair: any PartitionSpec entry whose mesh-axis size
    does not divide the corresponding dim is re-homed to the largest other
    unassigned dim it divides (e.g. batch=1 decode caches shard the sequence
    dim instead), else dropped. Keeps every (arch x shape x mesh) lowerable
    without per-case hand specs."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axis_size(e):
        names = (e,) if isinstance(e, str) else tuple(e)
        n = 1
        for nm in names:
            n *= sizes.get(nm, 1)
        return n

    def fit(spec, shp):
        shape = shp.shape
        entries = list(spec) + [None] * (len(shape) - len(spec))
        out = [None] * len(shape)
        homeless = []
        seen = set()
        for i, e in enumerate(entries[: len(shape)]):
            if e is None:
                continue
            names = (e,) if isinstance(e, str) else tuple(e)
            if any(n in seen for n in names):   # an axis may appear only once
                continue
            seen.update(names)
            if shape[i] % axis_size(e) == 0 and shape[i] >= axis_size(e):
                out[i] = e
            else:
                homeless.append(e)
        for e in homeless:
            n = axis_size(e)
            cands = [i for i in range(len(shape))
                     if out[i] is None and shape[i] % n == 0 and shape[i] >= n]
            if cands:
                out[max(cands, key=lambda i: shape[i])] = e
        return P(*out)

    return jax.tree.map(fit, spec_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, P))


def named(mesh, spec_tree):
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
