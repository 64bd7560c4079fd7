"""Worker for the 4-process pipeline-parallel test (VERDICT r4 #10).

Launched by tests/test_distributed_multiproc.py with 4 processes of 2
CPU devices each (8 global). The mesh is (dp=2, pp=4) laid out so every
pp ring CROSSES process boundaries — the GPipe ppermute hops ride the
gloo cross-process transport, the multi-host ICI/DCN analogue of the
reference's NCCL pipeline (reference runs pp via send/recv between
trainer processes).

Each process holds its pp stage's layer shard; params/opt/input global
arrays are assembled with jax.make_array_from_callback from identical
host-side values (same seed everywhere). Prints per-step losses.
"""
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_cpu_collectives_implementation', 'gloo')

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402

from paddle_tpu.models import transformer as T  # noqa: E402


def _globalize(tree, sharding_tree):
    def one(val, sh):
        val = np.asarray(val)
        return jax.make_array_from_callback(
            val.shape, sh, lambda idx: val[idx])
    return jax.tree_util.tree_map(one, tree, sharding_tree)


def main():
    pid = int(os.environ['PTPU_TRAINER_ID'])
    coord = os.environ['PTPU_COORD']
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=4, process_id=pid)
    assert jax.process_count() == 4, jax.process_count()
    assert len(jax.devices()) == 8, jax.devices()

    # (dp=2, pp=4): element [i, j] = devices[i*4 + j] -> each pp row
    # spans two processes (devices are process-major)
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ('dp', 'pp'))
    procs_per_ring = {
        d.process_index for d in mesh.devices[0]}
    assert len(procs_per_ring) > 1, "pp ring does not cross processes"

    cfg = T.TransformerConfig(vocab=128, d_model=64, n_heads=4,
                              n_layers=4, d_ff=128, max_len=32,
                              dtype=jnp.float32)
    host_params = T.stack_pipeline_params(T.init_params(cfg, seed=0),
                                          cfg, 4)
    from jax.sharding import PartitionSpec as P
    pspecs = T.pipeline_param_specs(cfg, 4, mesh)
    param_sh = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    params = _globalize(host_params, param_sh)
    # reuse the model's own optimizer-state factory so dtypes/fields
    # can never drift from the single-process oracle
    host_opt = jax.tree_util.tree_map(np.asarray,
                                      T.init_adam_state(host_params))
    opt_sh = {'m': param_sh, 'v': param_sh,
              't': NamedSharding(mesh, jax.sharding.PartitionSpec())}
    opt = _globalize(host_opt, opt_sh)

    step = T.make_pipeline_train_step(cfg, mesh, lr=1e-3, n_micro=2)
    rng = np.random.RandomState(7)
    tokens = rng.randint(0, cfg.vocab, size=(4, 33)).astype(np.int32)
    tok_sh = NamedSharding(mesh, jax.sharding.PartitionSpec('dp'))
    inputs = _globalize(tokens[:, :-1], tok_sh)
    targets = _globalize(tokens[:, 1:], tok_sh)

    losses = []
    with mesh:
        for _ in range(3):
            l, params, opt = step(params, opt, inputs, targets)
            losses.append(float(np.asarray(l)))
    print('PP_LOSSES=%s' % json.dumps(losses))


if __name__ == '__main__':
    main()
