"""Record the small trace the reducer's test reads
(``tests/tiny_trace.xplane.pb``): three steps of a two-operation jitted
function on whatever device JAX has, each step under the harness's
``feed``/``dispatch``/``fetch`` spans. Run once on the chip:

    python3 benchmark/chip/tests/record_tiny_trace.py <out dir>
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(out):
    x = jnp.ones((512, 512), jnp.bfloat16)
    f = jax.jit(lambda a: jnp.tanh(a @ a).sum())
    float(f(x))
    tdir = os.path.join(out, '_tiny_trace')
    jax.profiler.start_trace(tdir)
    for _ in range(3):
        with jax.profiler.TraceAnnotation('feed'):
            a = x
        with jax.profiler.TraceAnnotation('dispatch'):
            h = f(a)
        with jax.profiler.TraceAnnotation('fetch'):
            float(np.asarray(h))
        time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tdir, '**', '*.xplane.pb'),
                    recursive=True)[0]
    shutil.copy(src, os.path.join(out, 'tiny_trace.xplane.pb'))
    shutil.rmtree(tdir)
    print(jax.devices()[0].device_kind, os.path.getsize(
        os.path.join(out, 'tiny_trace.xplane.pb')), 'bytes')


if __name__ == '__main__':
    main(sys.argv[1])
