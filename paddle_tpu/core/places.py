"""Device places.

Parity: paddle/fluid/platform/place.h (CPUPlace/CUDAPlace/CUDAPinnedPlace).
BASELINE north star: add ``TPUPlace`` alongside. A place names one device
this process can address, and means what it says: ``TPUPlace(i)`` is the
i-th local TPU or a :class:`PlaceUnavailableError` — never another
backend, never ``i`` wrapped onto fewer devices. ``CUDAPlace`` is the
script-compatibility alias for "the backend JAX was started on", which
is also what ``place=None`` resolves to (:func:`default_place`).
"""

__all__ = ['TPUPlace', 'CPUPlace', 'CUDAPlace', 'CUDAPinnedPlace',
           'PlaceUnavailableError', 'default_place', 'on_tpu',
           'is_compiled_with_cuda', 'is_compiled_with_tpu']


class PlaceUnavailableError(RuntimeError):
    """The process has no such device: the platform is not one JAX was
    started on, or ``device_id`` is past its last local device."""


def _local_devices(platform):
    """Process-LOCAL devices of ``platform`` (None = the default
    backend), () when JAX has no such backend. A Place names a device
    this process can address: under jax.distributed, jax.devices() is
    the global list and device 0 may belong to another process."""
    import jax
    try:
        return tuple(jax.local_devices(backend=platform))
    except RuntimeError:
        return ()


def on_tpu():
    """THE predicate for "this process runs on the chip": AMP's auto
    mode and every Pallas engagement policy ask this one question."""
    import jax
    return jax.default_backend() == 'tpu'


class Place(object):
    platform = None     # None -> the backend JAX was started on

    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        import jax
        devs = _local_devices(self.platform)
        if not devs:
            raise PlaceUnavailableError(
                '%r: this process has no %r platform (JAX default '
                'backend: %r)' % (self, self.platform,
                                  jax.default_backend()))
        if not 0 <= self.device_id < len(devs):
            raise PlaceUnavailableError(
                '%r: device_id out of range, this process addresses %d '
                '%s device(s)' % (self, len(devs), devs[0].platform))
        return devs[self.device_id]

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)


class CPUPlace(Place):
    platform = 'cpu'


class TPUPlace(Place):
    platform = 'tpu'


class CUDAPlace(Place):
    """Compatibility alias: scripts written for CUDAPlace run on the
    backend JAX was started on."""


class CUDAPinnedPlace(CPUPlace):
    pass


def default_place():
    """What ``place=None`` means everywhere (Executor, ModelServer,
    Trainer): device 0 of the backend JAX was started on, under the
    name that says which — so a CPU-only process gets ``CPUPlace(0)``
    and says so, never a ``TPUPlace`` that quietly ran elsewhere."""
    import jax
    plat = jax.default_backend()
    if plat == 'tpu':
        return TPUPlace(0)
    if plat == 'cpu':
        return CPUPlace(0)
    return CUDAPlace(0)


def is_compiled_with_cuda():
    return bool(_local_devices('gpu'))


def is_compiled_with_tpu():
    return bool(_local_devices('tpu'))
