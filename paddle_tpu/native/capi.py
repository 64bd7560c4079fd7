"""Build/bind helper for the inference C API (capi.cc).

Parity: paddle/capi (C-linkage predictor). ``load()`` builds
libptpu_capi.so from capi.cc on first use (build.py) and returns a ctypes
handle with argtypes set — usable both for in-process testing and as
documentation of the C surface. C programs link the .so directly; see
tests/test_capi.py for a compiled-C-driver example.
"""
import ctypes
import os
import threading

from .build import ensure_built, load_library

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, 'libptpu_capi.so')
_LIB = None
_LOCK = threading.Lock()


_SOURCES = ('capi.cc',)


def build():
    return ensure_built('libptpu_capi.so', _SOURCES)


def load():
    """The bound library; raises :class:`build.NativeBuildError` (with
    the toolchain's message) when it cannot be built or loaded."""
    global _LIB
    if _LIB is not None:
        return _LIB
    with _LOCK:
        if _LIB is not None:
            return _LIB
        lib = load_library('libptpu_capi.so', _SOURCES)
        lib.ptpu_predictor_create.restype = ctypes.c_void_p
        lib.ptpu_predictor_create.argtypes = [ctypes.c_char_p]
        lib.ptpu_predictor_num_inputs.restype = ctypes.c_int
        lib.ptpu_predictor_num_inputs.argtypes = [ctypes.c_void_p]
        lib.ptpu_predictor_num_outputs.restype = ctypes.c_int
        lib.ptpu_predictor_num_outputs.argtypes = [ctypes.c_void_p]
        lib.ptpu_predictor_input_name.restype = ctypes.c_int
        lib.ptpu_predictor_input_name.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
        lib.ptpu_predictor_run_f32.restype = ctypes.c_int64
        lib.ptpu_predictor_run_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int,
            ctypes.POINTER(ctypes.c_int)]
        lib.ptpu_predictor_destroy.argtypes = [ctypes.c_void_p]
        lib.ptpu_last_error.restype = ctypes.c_char_p
        _LIB = lib
        return _LIB
