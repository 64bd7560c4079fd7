"""Device-mesh management.

The TPU replacement for the reference's device list + NCCL communicator
bootstrap (paddle/fluid/platform/nccl_helper.h): a jax.sharding.Mesh whose
axes name the parallelism kinds (dp = data, mp = tensor, pp = pipeline
stage, sp = sequence). Collectives ride ICI within a host's mesh slice and
DCN across hosts — placement is XLA's job once shardings are annotated.
"""
import numpy as np

_current_mesh = None


def clean_spec(spec, mesh, ndim=None):
    """Sanitize a Variable.sharding tuple against a mesh: axis names not in
    the mesh degrade to None (replicated on that dim); optionally truncate
    to ndim. Shared by ParallelExecutor in_shardings and the lowering's
    with_sharding_constraint pass so both interpret specs identically."""
    axes = set(mesh.axis_names)

    def clean(entry):
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axes)
            return kept or None
        return entry if entry in axes else None

    out = [clean(e) for e in spec]
    if ndim is not None:
        out = out[:ndim]
    return out


def set_mesh(mesh):
    global _current_mesh
    _current_mesh = mesh
    return mesh


def get_mesh(num_devices=None, axes=None, shape=None):
    """Build (or return the cached) mesh.

    axes defaults to 1-D ('dp',). Pass shape=dict(dp=4, mp=2) for
    multi-axis meshes.
    """
    global _current_mesh
    import jax
    from jax.sharding import Mesh
    if _current_mesh is not None and num_devices is None and shape is None:
        return _current_mesh
    # the GLOBAL device list, on purpose: under jax.distributed a dp
    # mesh spans every process's devices (Partitioner.globalize feeds
    # it); in one process it is the local list. A Place, by contrast,
    # names a local device (core/places.py).
    devices = jax.devices()
    if shape:
        axes = tuple(shape.keys())
        dims = tuple(shape.values())
        n = int(np.prod(dims))
        mesh = Mesh(np.asarray(devices[:n]).reshape(dims), axes)
    else:
        n = num_devices or len(devices)
        axes = axes or ('dp',)
        mesh = Mesh(np.asarray(devices[:n]).reshape((n,)), axes)
    _current_mesh = mesh
    return mesh
