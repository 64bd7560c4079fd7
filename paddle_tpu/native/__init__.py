"""Native (C++) runtime components: recordio reader + prefetch loader,
pinned host arena, inference C API. Built from source on first use
(build.py); the readers fall back to Python, with a warning, when the
toolchain is missing."""
from . import loader  # noqa
