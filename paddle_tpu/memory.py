"""Memory introspection + pinned host arena.

Parity: paddle/fluid/memory/memory.h (memory::Used, the buddy allocator
stats) and platform/cpu_info / gpu_info. On TPU the device allocator
belongs to XLA, so introspection surfaces the PJRT ``memory_stats`` of
the device (HBM bytes in use / peak / limit); what the framework still
allocates itself is HOST staging memory, covered by :class:`HostArena`
(mlock'ed bump arena in native/arena.cc).
"""
import ctypes

import numpy as np

__all__ = ['memory_stats', 'memory_allocated', 'max_memory_allocated',
           'HostArena']


def _device(place=None):
    import jax
    if place is not None and hasattr(place, 'jax_device'):
        return place.jax_device()
    return jax.local_devices()[0]


def memory_stats(place=None):
    """Device memory statistics as a dict (bytes).

    Keys (when the backend reports them): ``bytes_in_use``,
    ``peak_bytes_in_use``, ``bytes_limit``, ``largest_alloc_size``, plus
    whatever else PJRT exposes. Backends without allocator stats (CPU)
    return ``{'bytes_in_use': 0, 'supported': False}``.
    """
    import jax
    dev = _device(place)
    stats = dev.memory_stats()
    if not stats:
        # Backend without allocator stats (CPU): count live jax.Array
        # bytes resident on this device instead.
        live = sum(arr.nbytes // len(arr.devices())
                   for arr in jax.live_arrays() if dev in arr.devices())
        return {'bytes_in_use': live, 'supported': False,
                'source': 'live_arrays'}
    out = dict(stats)
    out['supported'] = True
    return out


def memory_allocated(place=None):
    """Bytes currently allocated on the device (0 if unsupported)."""
    return int(memory_stats(place).get('bytes_in_use', 0))


def max_memory_allocated(place=None):
    """Peak bytes allocated on the device (0 if unsupported)."""
    return int(memory_stats(place).get('peak_bytes_in_use', 0))


class _ArenaArray(np.ndarray):
    """ndarray view over arena memory; keeps the owning arena alive so
    its pages cannot be munmap'ed while the view is outstanding."""
    _arena_ref = None


class HostArena(object):
    """Pinned host-memory bump arena (native/arena.cc).

    Allocation returns numpy arrays backed by mlock'ed pages; ``reset()``
    recycles every buffer at once (typical use: one reset per training
    step, between staging batches). Falls back to plain numpy when the
    native library is unavailable.
    """

    def __init__(self, chunk_bytes=8 << 20):
        from .native import loader
        self._lib = loader._load() if loader.available() else None
        self._handle = None
        self._views = {}   # id(view) -> weakref (ndarray isn't hashable)
        if self._lib is not None:
            try:
                self._lib.arena_create.restype = ctypes.c_void_p
                self._lib.arena_create.argtypes = [ctypes.c_uint64]
                self._lib.arena_alloc.restype = ctypes.c_void_p
                self._lib.arena_alloc.argtypes = [
                    ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]
                self._lib.arena_reset.argtypes = [ctypes.c_void_p]
                self._lib.arena_stats.restype = ctypes.c_int
                self._lib.arena_stats.argtypes = [
                    ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
                    ctypes.POINTER(ctypes.c_uint64),
                    ctypes.POINTER(ctypes.c_uint64),
                    ctypes.POINTER(ctypes.c_int)]
                self._lib.arena_destroy.argtypes = [ctypes.c_void_p]
                self._handle = self._lib.arena_create(chunk_bytes)
            except Exception:
                self._handle = None

    @property
    def native(self):
        return self._handle is not None

    def alloc(self, shape, dtype='float32', align=64):
        """A numpy array over arena memory (invalidated by reset())."""
        dtype = np.dtype(dtype)
        size = int(np.prod(shape)) * dtype.itemsize
        if self._handle is None:
            return np.empty(shape, dtype)
        ptr = self._lib.arena_alloc(self._handle, size, align)
        if not ptr:
            return np.empty(shape, dtype)
        buf = (ctypes.c_uint8 * size).from_address(ptr)
        arr = np.frombuffer(buf, dtype=dtype).reshape(shape)
        arr = arr.view(_ArenaArray)
        arr._arena_ref = self   # views pin the arena's pages alive
        import weakref
        key = id(arr)
        self._views[key] = weakref.ref(
            arr, lambda _r, k=key, v=self._views: v.pop(k, None))
        return arr

    def reset(self):
        if self._handle is not None:
            self._lib.arena_reset(self._handle)

    def stats(self):
        """dict: allocated/peak/capacity bytes, chunks, pinned."""
        if self._handle is None:
            return {'allocated': 0, 'peak': 0, 'capacity': 0,
                    'chunks': 0, 'pinned': False, 'native': False}
        alloc = ctypes.c_uint64()
        peak = ctypes.c_uint64()
        cap = ctypes.c_uint64()
        pinned = ctypes.c_int()
        chunks = self._lib.arena_stats(
            self._handle, ctypes.byref(alloc), ctypes.byref(peak),
            ctypes.byref(cap), ctypes.byref(pinned))
        return {'allocated': alloc.value, 'peak': peak.value,
                'capacity': cap.value, 'chunks': chunks,
                'pinned': bool(pinned.value), 'native': True}

    def close(self):
        """Unmap the arena. Refuses while alloc()'d views are alive —
        a munmap under an outstanding view would be a segfault, not an
        exception."""
        if self._handle is None:
            return
        if len(self._views):
            raise RuntimeError(
                "HostArena.close(): %d allocated view(s) still alive; "
                "drop them (or let them be garbage-collected) first"
                % len(self._views))
        self._lib.arena_destroy(self._handle)
        self._handle = None

    def __del__(self):
        # GC only runs this when no view holds _arena_ref, so the
        # outstanding-views check cannot fire spuriously here.
        try:
            self.close()
        except Exception:
            pass
