"""Build the native libraries from their sources on first use.

The ``.so`` files are generated, git-ignored artefacts. One found on
disk is trusted only if its stamp says it was built from exactly the
sources present, by this interpreter — not because its mtime is newer:
a working tree can be copied to another machine with a stale binary in
it (libptpu_capi.so links against one Python's libpython).
"""
import ctypes
import hashlib
import os
import subprocess
import sys

__all__ = ['NativeBuildError', 'ensure_built', 'load_library']

_HERE = os.path.dirname(os.path.abspath(__file__))


class NativeBuildError(RuntimeError):
    """A native library was asked for and could not be built."""


def _digest(paths):
    h = hashlib.sha256(sys.version.encode())
    for path in paths:
        with open(path, 'rb') as f:
            h.update(f.read())
    return h.hexdigest()


def ensure_built(target, sources):
    """Path of the library ``target`` beside this file, (re)built with
    make unless its stamp matches ``sources`` + the Makefile + this
    interpreter. Raises :class:`NativeBuildError` carrying the
    toolchain's own words when the build fails."""
    lib = os.path.join(_HERE, target)
    stamp = lib + '.stamp'
    want = _digest([os.path.join(_HERE, s) for s in sources]
                   + [os.path.join(_HERE, 'Makefile')])
    try:
        with open(stamp) as f:
            have = f.read().strip()
    except OSError:
        have = None
    if have == want and os.path.exists(lib):
        return lib
    try:
        proc = subprocess.run(['make', '-s', '-B', '-C', _HERE, target],
                              capture_output=True, text=True)
    except OSError as e:
        raise NativeBuildError(
            'cannot build %s: running make failed: %s' % (target, e)) \
            from e
    if proc.returncode != 0:
        raise NativeBuildError(
            'cannot build %s (make exited %d):\n%s'
            % (target, proc.returncode,
               (proc.stderr or proc.stdout)[-2000:]))
    with open(stamp, 'w') as f:
        f.write(want)
    return lib


def load_library(target, sources):
    """ctypes handle of ``target``, built first if need be. Build and
    load failures both raise :class:`NativeBuildError`."""
    path = ensure_built(target, sources)
    try:
        return ctypes.CDLL(path)
    except OSError as e:
        raise NativeBuildError('cannot load %s: %s' % (target, e)) from e
