"""Flagship transformer: ring attention == dense attention; sharded train
step runs and improves loss on all mesh shapes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.models import transformer as T


def test_ring_attention_matches_dense():
    from jax.sharding import Mesh
    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(4), ('sp',))
    B, Tlen, H, Dh = 2, 32, 2, 8
    rng = np.random.RandomState(0)
    q = rng.randn(B, Tlen, H, Dh).astype(np.float32)
    k = rng.randn(B, Tlen, H, Dh).astype(np.float32)
    v = rng.randn(B, Tlen, H, Dh).astype(np.float32)

    dense = T._causal_attention(jnp.asarray(q), jnp.asarray(k),
                                jnp.asarray(v))

    from jax.sharding import PartitionSpec as P
    ring = jax.jit(jax.shard_map(
        lambda a, b, c: T.ring_attention(a, b, c, 'sp'),
        mesh=mesh, in_specs=(P(None, 'sp'),) * 3,
        out_specs=P(None, 'sp'), check_vma=False))(q, k, v)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize('shape', [(1, 1, 1), (2, 2, 2), (1, 2, 4)])
def test_train_step_converges(shape):
    from jax.sharding import Mesh
    dp, tp, sp = shape
    n = dp * tp * sp
    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(shape),
                ('dp', 'tp', 'sp'))
    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=2, n_layers=2,
                              d_ff=64, max_len=32, dtype=jnp.float32)
    params = T.shard_params(T.init_params(cfg, 0), cfg, mesh)
    opt = T.init_adam_state(params)
    step = T.make_train_step(cfg, mesh, lr=1e-2)
    toks = np.random.RandomState(0).randint(
        0, cfg.vocab, size=(2 * dp, 17)).astype(np.int32)
    x, y = toks[:, :-1], toks[:, 1:]
    losses = []
    for _ in range(20):
        loss, params, opt = step(params, opt, x, y)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses


def test_ring_attention_gradients_match_full_attention():
    """jax.grad through the whole ring composition (switch + finite
    sentinel + logsumexp merge + scan/ppermute) vs plain attention."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.models import transformer as T
    from paddle_tpu.ops import pallas_kernels as pk

    devs = np.asarray(jax.devices()[:4]).reshape(4,)
    mesh = Mesh(devs, ('sp',))
    rng = np.random.RandomState(3)
    B, Tt, H, D = 2, 64, 2, 16
    q = jnp.asarray(rng.randn(B, Tt, H, D) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, Tt, H, D) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, Tt, H, D) * 0.5, jnp.float32)
    go = jnp.asarray(rng.randn(B, Tt, H, D) * 0.1, jnp.float32)

    ring = jax.shard_map(lambda q, k, v: T.ring_attention(q, k, v, 'sp'),
                         mesh=mesh, in_specs=(P(None, 'sp'),) * 3,
                         out_specs=P(None, 'sp'), check_vma=False)
    g_ring = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(ring(q, k, v) * go),
        argnums=(0, 1, 2)))(q, k, v)
    g_full = jax.grad(
        lambda q, k, v: jnp.sum(
            pk.attention_reference(q, k, v, True) * go),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=5e-5)
        assert bool(jnp.isfinite(a).all())


def test_pipeline_parallel_matches_single_device():
    """pp=2 x dp=2 GPipe pipeline: first loss identical to the
    single-device forward, and 3 Adam steps produce the same params."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.models import transformer as T

    # f32: the parity check is exact (bf16 reorders rounding ~1%)
    cfg = T.TransformerConfig(vocab=512, d_model=64, n_heads=2,
                              n_layers=4, d_ff=128, max_len=128,
                              dtype=jnp.float32)
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab, (8, 65)).astype(np.int32)
    inputs = jnp.asarray(toks[:, :-1])
    targets = jnp.asarray(toks[:, 1:])

    ref_p = T.init_params(cfg, seed=0)
    ref_loss = float(T.loss_fn(ref_p, inputs, targets, cfg))
    ro = T.init_adam_state(ref_p)
    for _ in range(3):
        _, g = jax.value_and_grad(T.loss_fn)(ref_p, inputs, targets,
                                             cfg)
        ref_p, ro = T._adam_update(ref_p, g, ro, 1e-3)
    ref_stacked = T.stack_pipeline_params(ref_p, cfg, 2)

    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ('dp', 'pp'))
    step = T.make_pipeline_train_step(cfg, mesh, lr=1e-3, n_micro=2)
    p = T.stack_pipeline_params(T.init_params(cfg, seed=0), cfg, 2)
    o = T.init_adam_state(p)
    with mesh:
        losses = []
        for _ in range(3):
            l, p, o = step(p, o, inputs, targets)
            losses.append(float(l))
    assert abs(losses[0] - ref_loss) < 1e-4
    assert losses[-1] < losses[0]
    err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(ref_stacked),
        jax.tree_util.tree_leaves(p)))
    assert err < 1e-4, err


def test_pipeline_stack_roundtrip():
    import jax
    from paddle_tpu.models import transformer as T
    cfg = T.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                              n_layers=4, d_ff=32, max_len=32)
    params = T.init_params(cfg, seed=1)
    back = T.unstack_pipeline_params(
        T.stack_pipeline_params(params, cfg, 2), cfg)
    for k in params:
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b),
            params[k], back[k])
