"""Rehearsal 3: compile a cell's step program at its real size for the
described ``v5e:2x2`` topology, without the chip, and print XLA's
``memory_analysis()`` of it and the Mosaic calls it holds. A script for
the builder's hands, not a test: it steers the program's "am I on the
chip" predicate from here, as the on-chip-measurement guide asks.

    JAX_PLATFORMS=cpu python3 benchmark/chip/tests/compile_for_chip.py \
        <workload> [num_hidden_layers]
"""
import json
import os
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, HERE]


def main():
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core import places
    from paddle_tpu.core.lowering import lower_block
    import harness
    import manifest

    places.on_tpu = lambda: True        # AMP and kernel engagement
    man = manifest.Manifest(time.perf_counter())
    cell = man.workload(sys.argv[1])
    cfg = man.config(cell['config'])
    if len(sys.argv) > 2:
        cfg['num_hidden_layers'] = int(sys.argv[2])
    traffic = man.traffic(cell['traffic'])
    model = harness.model_module(cfg)
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    built = model.build(cfg, traffic)
    main_p, loss = built['main'], built['loss']
    scope = fluid.Scope()
    batch = {k: np.asarray(v) for k, v in model.draw_batch(
        cfg, traffic, jax.random.PRNGKey(0)).items()}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace(0))
        exe.run(built['startup'])
        if cell['chips'] > 1:
            mesh = Mesh(np.array(topo.devices[:cell['chips']]), ('dp',))
            pe = fluid.ParallelExecutor(loss_name=loss.name,
                                        main_program=main_p, mesh=mesh)
            part, exe = pe.partitioner, pe._exe
        fetch_names, feed, s_in, s_out, static_env = exe._prep_lowering(
            main_p, batch, [loss], scope, consume_readers=False)
        prog = exe._optimized_program(main_p, fetch_names, scope=scope)
        fn = lower_block(prog, prog.global_block(), sorted(feed.keys()),
                         fetch_names, s_in, s_out, static_env=static_env)
        state = {n: scope.raw(n) for n in s_in}
        if cell['chips'] > 1:
            feeds_s = part.feed_shardings(feed)
            state_s = part.state_shardings(main_p, s_in)
            out_s = part.state_shardings(main_p, s_out)
            jitted = part.partition(
                part.trace_wrap(fn), in_shardings=(feeds_s, state_s),
                out_shardings=(part.replicated, out_s), donate_argnums=(1,))
            avals = (
                {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                         sharding=feeds_s[k])
                 for k, v in feed.items()},
                {k: jax.ShapeDtypeStruct(np.shape(v), np.asarray(v).dtype,
                                         sharding=state_s[k])
                 for k, v in state.items()})
            ctx = part.run_context()
        else:
            one = SingleDeviceSharding(topo.devices[0])
            jitted = jax.jit(fn, donate_argnums=(1,))
            avals = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(
                    np.shape(v), np.asarray(v).dtype, sharding=one),
                (feed, state))
            import contextlib
            ctx = contextlib.nullcontext()
        t = time.perf_counter()
        with ctx:
            comp = jitted.lower(*avals).compile()
        secs = time.perf_counter() - t
    ma = comp.memory_analysis()
    text = comp.as_text()
    if os.environ.get('DUMP_HLO'):
        with open(os.environ['DUMP_HLO'], 'w') as f:
            f.write(text)
    out = {'workload': sys.argv[1], 'compile_s': secs,
           'layers': cfg.get('num_hidden_layers'),
           'argument_bytes': ma.argument_size_in_bytes,
           'output_bytes': ma.output_size_in_bytes,
           'alias_bytes': ma.alias_size_in_bytes,
           'temp_bytes': ma.temp_size_in_bytes,
           'tpu_custom_call': text.count('custom_call_target="tpu_custom_call"'),
           'all_reduce': text.count(' all-reduce('),
           'reduce_scatter': text.count(' reduce-scatter('),
           # the TPU compiler's reduce-scatter: a fusion that calls an
           # all-reduce-scatter computation (its all-reduce is counted
           # above too)
           'all_reduce_scatter': text.count('calls=%all-reduce-scatter'),
           'all_gather': text.count(' all-gather(')}
    out['live_bytes'] = (out['argument_bytes'] + out['output_bytes']
                         - out['alias_bytes'] + out['temp_bytes'])
    print(json.dumps(out))


if __name__ == '__main__':
    main()
