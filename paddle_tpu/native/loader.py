"""ctypes binding for the native recordio reader/writer + prefetch
loader (recordio.cc). Built from source on first use (build.py); the
readers degrade to the pure-Python implementation in reader_io.py when
the toolchain is unavailable — and say so (pybind11 is not in this
image — plain ctypes).
"""
import ctypes
import threading
import warnings

from .build import NativeBuildError, load_library

_LIB = None
_BUILD_LOCK = threading.Lock()
_BUILD_ERROR = None


def _load():
    """The bound library; raises :class:`NativeBuildError` (with the
    toolchain's message) when it cannot be built or loaded."""
    global _LIB, _BUILD_ERROR
    if _LIB is not None:
        return _LIB
    with _BUILD_LOCK:
        if _LIB is not None:
            return _LIB
        if _BUILD_ERROR is not None:
            raise _BUILD_ERROR
        try:
            lib = load_library('librecordio.so',
                               ('recordio.cc', 'arena.cc'))
        except NativeBuildError as e:
            _BUILD_ERROR = e      # do not re-run make on every call
            raise
        lib.rio_open.restype = ctypes.c_void_p
        lib.rio_open.argtypes = [ctypes.c_char_p]
        lib.rio_next.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.rio_next.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.c_uint64)]
        lib.rio_error.restype = ctypes.c_char_p
        lib.rio_error.argtypes = [ctypes.c_void_p]
        lib.rio_close.argtypes = [ctypes.c_void_p]
        lib.rio_writer_open.restype = ctypes.c_void_p
        lib.rio_writer_open.argtypes = [ctypes.c_char_p]
        lib.rio_write.restype = ctypes.c_int
        lib.rio_write.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint8),
                                  ctypes.c_uint64]
        lib.rio_writer_close.restype = ctypes.c_uint64
        lib.rio_writer_close.argtypes = [ctypes.c_void_p]
        lib.loader_create.restype = ctypes.c_void_p
        lib.loader_create.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int, ctypes.c_int,
            ctypes.c_int, ctypes.c_int]
        lib.loader_next.restype = ctypes.POINTER(ctypes.c_uint8)
        lib.loader_next.argtypes = [ctypes.c_void_p,
                                    ctypes.POINTER(ctypes.c_uint64)]
        lib.loader_error.restype = ctypes.c_int
        lib.loader_error.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                     ctypes.c_int]
        lib.loader_destroy.argtypes = [ctypes.c_void_p]
        _LIB = lib
    return _LIB


def available():
    """True when the native library is usable. False — with a warning
    carrying the build error — when it is not and callers should take
    their pure-Python path."""
    try:
        _load()
    except NativeBuildError as e:
        warnings.warn('native loader unavailable, using the pure-Python '
                      'readers: %s' % e, RuntimeWarning, stacklevel=2)
        return False
    return True


def read_records(path):
    """Generator over raw record payload bytes (native crc32 checked)."""
    lib = _load()
    h = lib.rio_open(path.encode())
    if not h:
        raise IOError("%s is not a paddle_tpu recordio file" % path)
    try:
        n = ctypes.c_uint64()
        while True:
            ptr = lib.rio_next(h, ctypes.byref(n))
            if not ptr:
                err = lib.rio_error(h).decode()
                if err:
                    raise IOError("recordio %s in %s" % (err, path))
                return
            yield ctypes.string_at(ptr, n.value)
    finally:
        lib.rio_close(h)


def write_records(path, payloads):
    """Write payload byte strings; returns the record count."""
    lib = _load()
    h = lib.rio_writer_open(path.encode())
    if not h:
        raise IOError("cannot open %s for writing" % path)
    for p in payloads:
        buf = (ctypes.c_uint8 * len(p)).from_buffer_copy(p)
        if lib.rio_write(h, buf, len(p)) != 0:
            lib.rio_writer_close(h)
            raise IOError("short write to %s" % path)
    return int(lib.rio_writer_close(h))


class PrefetchLoader(object):
    """Background-thread record prefetcher over one or more files.

    Parity: the reference's double_buffer reader + recordio scanner —
    disk IO and checksum overlap with device compute. Iterate to get
    payload bytes.
    """

    def __init__(self, filenames, n_threads=2, capacity=64, passes=1):
        if isinstance(filenames, str):
            filenames = [filenames]
        self._filenames = filenames
        self._n_threads = n_threads
        self._capacity = capacity
        self._passes = passes
        self._h = None

    def __iter__(self):
        if not available():
            # degraded mode: plain sequential python reads
            from ..reader_io import read_records as py_read
            for _ in range(self._passes):
                for fn in self._filenames:
                    for payload in py_read(fn):
                        yield payload
            return
        lib = _load()
        arr = (ctypes.c_char_p * len(self._filenames))(
            *[f.encode() for f in self._filenames])
        h = lib.loader_create(arr, len(self._filenames),
                              self._n_threads, self._capacity,
                              self._passes)
        try:
            n = ctypes.c_uint64()
            while True:
                ptr = lib.loader_next(h, ctypes.byref(n))
                if not ptr:
                    break
                yield ctypes.string_at(ptr, n.value)
            msg = ctypes.create_string_buffer(512)
            if lib.loader_error(h, msg, len(msg)) > 0:
                raise IOError("prefetch loader: %s"
                              % msg.value.decode(errors='replace'))
        finally:
            lib.loader_destroy(h)
