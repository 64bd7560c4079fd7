from .places import (TPUPlace, CPUPlace, CUDAPlace, CUDAPinnedPlace,  # noqa
                     PlaceUnavailableError, is_compiled_with_cuda,
                     is_compiled_with_tpu)
from .registry import register_kernel, get_kernel, has_kernel  # noqa


class EOFException(Exception):
    """Raised when a program reader runs out of data (parity:
    paddle/fluid/framework/reader.h EOF semantics)."""
    pass


def __getattr__(name):
    # Reference scripts reach runtime types through ``fluid.core``
    # (e.g. fluid.core.Scope() in test_fit_a_line.py:103). Resolve them
    # lazily — executor imports this package, so an eager import would
    # be circular.
    if name in ('Scope',):
        from ..executor import Scope
        return Scope
    if name in ('LoDTensor',):
        from ..lod import SequenceTensor
        return SequenceTensor
    raise AttributeError(name)
