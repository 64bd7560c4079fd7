"""The benchmark's own tests run by path on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/chip/tests -q -p no:cacheprovider

They describe no TPU topology and need no chip. Four virtual CPU devices
stand in for the four-chip cell.
"""
import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=4').strip()
os.environ.setdefault('JAX_ENABLE_COMPILATION_CACHE', 'false')

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (ROOT, CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)
