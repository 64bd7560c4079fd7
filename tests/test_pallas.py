"""Pallas kernels vs their XLA-fallback math (SURVEY.md §2.2: fused LSTM
cell + flash attention). On CPU the Pallas path runs with interpret=True,
so the kernel bodies themselves are exercised."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_kernels as pk


# (H, D, block_q, block_k, T): two heads to a 128-lane block at D = 64
# (one and two head groups), one head a block at D = 128 and 256, and
# block_q != block_k either way round (a tile on the diagonal goes
# whole under the mask, as a square one of 256 or 512 rows does); then
# square blocks of 384 and 768 rows, whose tiles go in 3 row chunks of
# 128 and 256 rows (pk._row_chunks), at T = 1x, 2x and 4x the block:
# diagonal tiles only; diagonal and full ones; dead ones too; and one
# of 1024 rows, in 2 chunks of 512
_HEAD_CASES = [(2, 64, 128, 128, 256), (4, 64, 128, 256, 256),
               (4, 64, 256, 128, 256), (1, 128, 128, 256, 256),
               (2, 128, 256, 128, 256), (1, 256, 128, 128, 256),
               (4, 64, 256, 256, 512), (4, 64, 384, 384, 384),
               (4, 64, 384, 384, 768), (2, 64, 384, 384, 1536),
               (1, 128, 384, 384, 768), (1, 128, 384, 384, 1536),
               (2, 64, 512, 512, 1024), (1, 128, 768, 768, 1536),
               (2, 64, 1024, 1024, 1024)]
_HEAD_IDS = ['h%d-d%d-%dx%d-t%d' % c for c in _HEAD_CASES]


@pytest.mark.parametrize('H,D,bq,bk,T', _HEAD_CASES, ids=_HEAD_IDS)
@pytest.mark.parametrize('causal', [True, False])
def test_flash_attention_matches_reference(causal, H, D, bq, bk, T):
    rng = np.random.RandomState(0)
    B = 2
    q = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    k = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    v = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    assert pk.flash_plan(q, bq, bk, interpret=True) == (bq, bk)
    ref = pk.attention_reference(q, k, v, causal=causal)
    out = pk.flash_attention(q, k, v, causal=causal, block_q=bq,
                             block_k=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_causality():
    rng = np.random.RandomState(1)
    B, T, H, D = 1, 256, 2, 64
    q = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    k = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    v = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    base = pk.flash_attention(q, k, v, causal=True, block_q=128,
                              block_k=128, interpret=True)
    # perturbing the FUTURE must not change past outputs
    k2 = k.at[:, T // 2:].set(0.0)
    v2 = v.at[:, T // 2:].set(9.0)
    pert = pk.flash_attention(q, k2, v2, causal=True, block_q=128,
                              block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(base[:, :T // 2]),
                               np.asarray(pert[:, :T // 2]),
                               rtol=1e-5, atol=1e-6)


def test_fused_lstm_cell_matches_reference():
    rng = np.random.RandomState(2)
    B, H = 4, 8
    xg = jnp.asarray(rng.randn(B, 4 * H).astype('float32'))
    r = jnp.asarray(rng.randn(B, H).astype('float32'))
    c = jnp.asarray(rng.randn(B, H).astype('float32'))
    w = jnp.asarray((rng.randn(H, 4 * H) * 0.3).astype('float32'))
    h_ref, c_ref = pk._lstm_cell_reference(xg, r, c, w)
    h_out, c_out = pk.fused_lstm_cell(xg, r, c, w, interpret=True)
    np.testing.assert_allclose(np.asarray(h_out), np.asarray(h_ref),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(c_out), np.asarray(c_ref),
                               rtol=2e-5, atol=2e-6)


def test_flash_attention_is_differentiable():
    import jax
    rng = np.random.RandomState(3)
    B, T, H, D = 1, 256, 2, 64
    q = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    k = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    v = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))

    def loss_pallas(q, k, v):
        return jnp.sum(pk.flash_attention(q, k, v, causal=True,
                                          block_q=128, block_k=128,
                                          interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(pk.attention_reference(q, k, v, causal=True) ** 2)

    g_pallas = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_pallas, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_fused_lstm_cell_is_differentiable():
    import jax
    rng = np.random.RandomState(4)
    B, H = 2, 4
    xg = jnp.asarray(rng.randn(B, 4 * H).astype('float32'))
    r = jnp.asarray(rng.randn(B, H).astype('float32'))
    c = jnp.asarray(rng.randn(B, H).astype('float32'))
    w = jnp.asarray((rng.randn(H, 4 * H) * 0.3).astype('float32'))

    def loss_pallas(xg, r, c, w):
        h, cn = pk.fused_lstm_cell(xg, r, c, w, interpret=True)
        return jnp.sum(h * cn)

    def loss_ref(xg, r, c, w):
        h, cn = pk._lstm_cell_reference(xg, r, c, w)
        return jnp.sum(h * cn)

    g_p = jax.grad(loss_pallas, argnums=(0, 1, 2, 3))(xg, r, c, w)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(xg, r, c, w)
    for a, b in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_pallas_path_engages_for_transformer_shapes(monkeypatch):
    """The kernel must actually fire for the flagship transformer's
    shapes (VERDICT r1: no test asserted the Pallas path engages)."""
    fired = []
    orig = pk._flash_lse

    def spy(*args, **kwargs):
        fired.append(True)
        return orig(*args, **kwargs)

    monkeypatch.setattr(pk, '_flash_lse', spy)
    rng = np.random.RandomState(5)
    B, T, H, D = 2, 512, 8, 64   # entry()'s flagship attention shape
    q = jnp.asarray(rng.randn(B, T, H, D).astype('float32'))
    pk.flash_attention(q, q, q, causal=True, interpret=True)
    assert fired, "Pallas path did not engage for T=512"
    # non-128-aligned T falls back to the XLA reference, silently
    fired.clear()
    q2 = jnp.asarray(rng.randn(B, 100, H, D).astype('float32'))
    pk.flash_attention(q2, q2, q2, causal=True, interpret=True)
    assert not fired
    # so does a head shape whose lanes do not fill 128-lane blocks: an
    # odd number of heads at D = 64, any D but 64 and multiples of 128
    for shape in ((B, T, 3, 64), (B, T, 8, 32), (B, T, 2, 192)):
        q3 = jnp.asarray(rng.randn(*shape).astype('float32'))
        assert pk.flash_plan(q3, interpret=True) is None
        pk.flash_attention(q3, q3, q3, causal=True, interpret=True)
        assert not fired


def _take_two_pass(monkeypatch):
    """Send every flash backward from here on down the two-pass route
    the way a long sequence is sent: by a dq slab over the cap."""
    monkeypatch.setattr(pk, '_MERGED_BWD_MAX_SLAB_BYTES', 0)


@pytest.fixture
def two_pass(request, monkeypatch):
    """The backward route a test asks for by ``request.param`` (True:
    the two-pass fallback; False: merged, as under the cap)."""
    if request.param:
        _take_two_pass(monkeypatch)
    return request.param


@pytest.mark.parametrize('two_pass', [False, True], indirect=True,
                         ids=['merged', 'two-pass'])
@pytest.mark.parametrize('H,D,block,T', [
    (2, 64, 128, 256), (4, 64, 384, 384), (4, 64, 384, 768),
    (2, 64, 384, 1536), (1, 128, 384, 768), (1, 128, 512, 1024),
    (2, 64, 1024, 2048)])
def test_flash_attention_bf16_grads(H, D, block, T, two_pass):
    """bf16 end-to-end through the Pallas forward and backward (the AMP
    path), whole and chunked diagonal tiles, two heads a program and
    one: output, lse and gradients (the lse cotangent among them)
    within 3e-2 of the float32 reference's largest magnitude
    (chip_smoke.py's kernel rule)."""
    rng = np.random.RandomState(6)
    q, k, v, go = (jnp.asarray(rng.randn(1, T, H, D) * 0.5, jnp.bfloat16)
                   for _ in range(4))
    gl = jnp.asarray(rng.randn(1, H, T) * 0.1, jnp.float32)

    def run(attend, *args):
        def loss(q, k, v):
            o, lse = attend(q, k, v)
            both = jnp.sum(o.astype(jnp.float32) * go.astype(jnp.float32)) \
                + jnp.sum(lse * gl)
            return both, (o, lse)
        (_, outs), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(*args)
        return outs + grads

    got = run(lambda q, k, v: pk.flash_attention_with_lse(
        q, k, v, block_q=block, block_k=block, interpret=True), q, k, v)
    want = run(pk.attention_reference_with_lse,
               *(x.astype(jnp.float32) for x in (q, k, v)))
    for name, a, b in zip(('out', 'lse', 'dq', 'dk', 'dv'), got, want):
        assert a.dtype == (jnp.float32 if name == 'lse' else jnp.bfloat16)
        a, b = np.asarray(a, np.float32), np.asarray(b)
        assert np.isfinite(a).all(), name
        err = np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6)
        assert err < 3e-2, '%s: %.3g' % (name, err)


def test_fused_lstm_engages_in_scan_with_grads(monkeypatch):
    """ADVICE r1: force the fused Pallas cell (interpret=True) through
    _lstm_scan inside a real training step — covers the
    scan + custom_vjp composition off-TPU — and match the reference
    cell's losses."""
    import paddle_tpu.fluid as fluid

    def build_and_train():
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = 3
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name='x', shape=[16], dtype='float32',
                                  lod_level=1)
            h, c = fluid.layers.dynamic_lstm(input=x, size=16,
                                             use_peepholes=False)
            last = fluid.layers.sequence_pool(h, 'last')
            loss = fluid.layers.mean(last)
            fluid.optimizer.SGD(learning_rate=0.05).minimize(loss)
        from paddle_tpu.lod import create_lod_tensor
        rng = np.random.RandomState(0)
        lens = [5, 3]
        rows = rng.randn(sum(lens), 16).astype('float32')
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            for _ in range(4):
                out = exe.run(main,
                              feed={'x': create_lod_tensor(rows,
                                                           [lens])},
                              fetch_list=[loss])[0]
                losses.append(float(np.asarray(out).mean()))
        return losses

    baseline = build_and_train()   # CPU -> reference cell

    calls = []
    orig = pk.fused_lstm_cell

    def forced(xg, r, c, w, interpret=None):
        calls.append(True)
        return orig(xg, r, c, w, interpret=True)

    monkeypatch.setattr(pk, 'fused_lstm_cell', forced)
    fused = build_and_train()      # Pallas kernel body via interpret
    assert calls, "fused path never engaged"
    np.testing.assert_allclose(fused, baseline, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize('H,D,bq,bk,T', _HEAD_CASES, ids=_HEAD_IDS)
@pytest.mark.parametrize('causal', [True, False])
def test_flash_with_lse_matches_reference_including_lse_grads(
        causal, H, D, bq, bk, T):
    """flash_attention_with_lse: out AND lse match, and gradients flow
    correctly through BOTH outputs (the lse cotangent folds into the
    backward's delta term — the ring-attention merge depends on it)."""
    import jax
    rng = np.random.RandomState(7)
    B = 2
    q = jnp.asarray(rng.randn(B, T, H, D) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, T, H, D) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, T, H, D) * 0.5, jnp.float32)
    go = jnp.asarray(rng.randn(B, T, H, D) * 0.1, jnp.float32)
    gl = jnp.asarray(rng.randn(B, H, T) * 0.1, jnp.float32)

    op, lp = pk.flash_attention_with_lse(
        q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=True)
    orf, lrf = pk.attention_reference_with_lse(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(op), np.asarray(orf),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lp), np.asarray(lrf),
                               rtol=2e-4, atol=2e-5)

    def loss_p(q, k, v):
        o, l = pk.flash_attention_with_lse(
            q, k, v, causal=causal, block_q=bq, block_k=bk,
            interpret=True)
        return jnp.sum(o * go) + jnp.sum(l * gl)

    def loss_r(q, k, v):
        o, l = pk.attention_reference_with_lse(q, k, v, causal=causal)
        return jnp.sum(o * go) + jnp.sum(l * gl)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ring_attention_uses_flash_kernel(monkeypatch):
    """With 128-aligned local blocks the ring path really runs the
    Pallas kernel for its partials."""
    import jax
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.models import transformer as T

    fired = []
    orig = pk._flash_lse

    def spy(q, k, v, causal, bq, bk, interpret):
        fired.append(True)
        return orig(q, k, v, causal, bq, bk, interpret)

    # force kernel engagement off-TPU: route through interpret mode
    monkeypatch.setattr(
        pk, 'flash_attention_with_lse',
        lambda q, k, v, causal=True, **kw: spy(q, k, v, causal, 128,
                                               128, True))
    devs = np.asarray(jax.devices()[:2]).reshape(2,)
    mesh = Mesh(devs, ('sp',))
    rng = np.random.RandomState(1)
    B, Tt, H, D = 1, 256, 2, 64   # T_local = 128
    q = jnp.asarray(rng.randn(B, Tt, H, D) * 0.5, jnp.float32)
    ring = jax.shard_map(lambda q, k, v: T.ring_attention(q, k, v, 'sp'),
                         mesh=mesh,
                         in_specs=(P(None, 'sp'),) * 3,
                         out_specs=P(None, 'sp'), check_vma=False)
    out = np.asarray(jax.jit(ring)(q, q, q))
    assert fired, "Pallas kernel did not engage inside ring attention"
    ref = np.asarray(pk.attention_reference(q, q, q, causal=True))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.skipif(jax.default_backend() != 'tpu',
                    reason='Mosaic engagement is TPU-only')
def test_flash_attention_engages_mosaic_at_bench_shapes():
    """VERDICT r2 #3: prove the Pallas path actually engages (no silent
    XLA fallback) at long sequences."""
    import numpy as np
    from paddle_tpu.ops import pallas_kernels as P
    # engagement starts at _FLASH_MIN_T=768 (r4: strictly above the
    # measured break-even; T=512 deliberately falls back to XLA)
    for T in (1024, 2048, 4096):
        q = jnp.asarray(np.random.RandomState(0)
                        .randn(2, T, 4, 64).astype('float32'))
        hlo = jax.jit(lambda q: P.flash_attention(q, q, q)) \
            .lower(q).compile().as_text()
        assert 'tpu_custom_call' in hlo, 'no Mosaic call at T=%d' % T
    q = jnp.asarray(np.random.RandomState(0)
                    .randn(2, 512, 4, 64).astype('float32'))
    hlo = jax.jit(lambda q: P.flash_attention(q, q, q)) \
        .lower(q).compile().as_text()
    assert 'tpu_custom_call' not in hlo, \
        'T=512 must fall back to XLA (below break-even)'


def test_flash_attention_layer_scaling():
    """r3 review: the layer must NOT pre-scale q (the kernel applies
    1/sqrt(dh) itself). Single-head, non-causal == plain softmax attn."""
    import paddle_tpu.fluid as fluid
    rng = np.random.RandomState(5)
    B, T, D = 2, 16, 8
    q, k, v = [rng.randn(B, T, D).astype('float32') for _ in range(3)]
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        qv = fluid.layers.data(name='q', shape=[T, D], dtype='float32')
        kv = fluid.layers.data(name='k', shape=[T, D], dtype='float32')
        vv = fluid.layers.data(name='v', shape=[T, D], dtype='float32')
        o = fluid.layers.flash_attention(qv, kv, vv, num_heads=1,
                                         causal=False)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        got, = exe.run(main, feed={'q': q, 'k': k, 'v': v},
                       fetch_list=[o])
    s = np.einsum('btd,bsd->bts', q, k) / np.sqrt(D)
    e = np.exp(s - s.max(-1, keepdims=True))
    ref = np.einsum('bts,bsd->btd', e / e.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize('H,D,bq,bk,T', [
    (2, 64, 128, 256, 256), (4, 64, 128, 256, 256), (1, 128, 128, 256, 256),
    (4, 64, 384, 384, 768), (1, 128, 384, 384, 1536)])
@pytest.mark.parametrize("causal", [True, False])
def test_merged_backward_matches_two_pass(causal, H, D, bq, bk, T,
                                          monkeypatch):
    """The merged dkv+dq-partials backward must produce the same grads
    as the two-pass path (it is the default under the slab cap), whole
    and chunked diagonal tiles alike: they share one tile body."""
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(1, T, H, D), jnp.float32) * 0.1
    k = jnp.asarray(rng.randn(1, T, H, D), jnp.float32) * 0.1
    v = jnp.asarray(rng.randn(1, T, H, D), jnp.float32) * 0.1

    def grads():
        def loss(q, k, v):
            o = pk.flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk, interpret=True)
            return jnp.sum(o * 1e-2)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_merged = grads()
    _take_two_pass(monkeypatch)
    g_two = grads()
    for a, b in zip(g_merged, g_two):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize('H,D', [(2, 64), (1, 128)])
@pytest.mark.parametrize('causal', [True, False])
def test_flash_lse_grads_float32(causal, H, D, monkeypatch):
    """Float32 forward, lse and the gradients of both, merged and
    two-pass, against the reference at 384-row blocks and T = 4 blocks:
    dead, full and (under ``causal``) chunked diagonal tiles in one
    sweep."""
    rng = np.random.RandomState(8)
    T = 1536
    q, k, v, go = (jnp.asarray(rng.randn(1, T, H, D) * 0.5, jnp.float32)
                   for _ in range(4))
    gl = jnp.asarray(rng.randn(1, H, T) * 0.1, jnp.float32)

    def grads(attend):
        def loss(q, k, v):
            o, lse = attend(q, k, v, causal=causal)
            return jnp.sum(o * go) + jnp.sum(lse * gl), (o, lse)
        (_, outs), g = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return outs + g

    want = grads(pk.attention_reference_with_lse)
    for two_pass in (False, True):
        if two_pass:
            _take_two_pass(monkeypatch)
        got = grads(lambda q, k, v, causal: pk.flash_attention_with_lse(
            q, k, v, causal=causal, block_q=384, block_k=384,
            interpret=True))
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize('dtype,T,causal,plan,diag', [
    ('bfloat16', 2048, True, (2048, 2048), 'chunked4'),
    ('bfloat16', 1536, True, (1536, 1536), 'chunked3'),
    ('bfloat16', 4096, True, (1024, 1024), 'chunked2'),
    ('bfloat16', 2048, False, (1024, 1024), 'none'),
    ('float32', 2048, True, (512, 1024), 'whole')])
def test_flash_plan_default_blocks(dtype, T, causal, plan, diag):
    """flash_plan's own blocks: bf16 takes one tile a program where a
    causal sequence of at most 2048 positions is one (no tile lies
    below the diagonal, and a diagonal tile's row chunks fit VMEM),
    else 1024 x 1024, whose full tiles run whole; float32 keeps 512 x
    1024, a grid-valued diagonal offset, so the whole tile under the
    mask."""
    q = jnp.zeros((1, T, 4, 64), dtype)
    assert pk.flash_plan(q, interpret=True, causal=causal) == plan
    assert pk.flash_diag(plan, causal) == diag


# ---- the flash_attention op on AMP's MXU path -------------------------------
@pytest.fixture
def engage(monkeypatch):
    """The op's engaged route on the CPU: the engagement rule sees a TPU
    and no row floor, and the kernels run in the Pallas interpreter."""
    orig = pk._flash_lse
    monkeypatch.setattr(pk, '_on_tpu', lambda: True)
    monkeypatch.setattr(pk, '_FLASH_MIN_ROWS', 0)
    monkeypatch.setattr(
        pk, '_flash_lse',
        lambda q, k, v, causal, bq, bk, interpret, window=None:
            orig(q, k, v, causal, bq, bk, True, window))


_FLASH_OP_B, _FLASH_OP_H, _FLASH_OP_DH = 2, 4, 64


@contextlib.contextmanager
def _plan_blocks(blocks):
    """flash_plan under ``blocks`` = (block_q, block_k) where its caller
    names none (None: its own): how a test gives the op, which names
    none, more than one tile at a small T."""
    with pytest.MonkeyPatch.context() as patch:
        if blocks:
            orig = pk.flash_plan
            patch.setattr(
                pk, 'flash_plan',
                lambda q, block_q=None, block_k=None, interpret=None,
                causal=True, window=None: orig(
                    q, block_q or blocks[0], block_k or blocks[1],
                    interpret, causal, window))
        yield


def _flash_op_feed(T, seed=11, heads=_FLASH_OP_H):
    rng = np.random.RandomState(seed)
    shape = (_FLASH_OP_B, T, heads * _FLASH_OP_DH)
    return {n: rng.randn(*shape).astype('float32') * s
            for n, s in (('q', 1.0), ('k', 1.0), ('v', 1.0), ('w', 0.1))}


def _flash_op_program(T, depth=1, grads=True, heads=_FLASH_OP_H,
                      causal=True):
    """``depth`` flash_attention ops in a row on fed q, k, v (B2 D64,
    H4 unless ``heads`` says otherwise) and, with ``grads``, the
    gradients of sum(out * w) in q, k, v (fluid.gradients replays the
    op path under jax.vjp: a second trace of each op). Returns (main,
    startup, [out, dq, dk, dv])."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, k, v, w = [
            fluid.layers.data(name=n, shape=[T, heads * _FLASH_OP_DH],
                              dtype='float32') for n in 'qkvw']
        for x in (q, k, v):
            x.stop_gradient = False
        out = q
        for _ in range(depth):
            out = fluid.layers.flash_attention(out, k, v,
                                               num_heads=heads,
                                               causal=causal)
        fetch = [out]
        if grads:
            loss = fluid.layers.reduce_sum(
                fluid.layers.elementwise_mul(out, w))
            fetch += fluid.gradients(loss, [q, k, v])
    return main, startup, fetch


def _flash_op_run(T, blocks=None, lower_only=False, grads=True):
    """Run (or only lower) the one-op program: [out, dq, dk, dv] as
    numpy, or the lowered step's text."""
    import paddle_tpu.fluid as fluid
    main, startup, fetch = _flash_op_program(T, grads=grads)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()), \
            _plan_blocks(blocks and (blocks, blocks)):
        exe.run(startup)
        if lower_only:
            return exe.lowered(main, feed=_flash_op_feed(T),
                               fetch_list=fetch).as_text()
        return exe.run(main, feed=_flash_op_feed(T), fetch_list=fetch)


def _flash_op_direct(T, attend):
    """[out, dq, dk, dv] of ``attend`` (attention_reference, or
    flash_attention as the op's body called it before it joined AMP)
    called directly on the same float32 feed, heads split as the op
    splits them."""
    feed = {n: jnp.asarray(x) for n, x in _flash_op_feed(T).items()}
    heads = (_FLASH_OP_B, T, _FLASH_OP_H, _FLASH_OP_DH)

    def loss(q, k, v):
        o = attend(q.reshape(heads), k.reshape(heads), v.reshape(heads),
                   causal=True).reshape(q.shape)
        return jnp.sum(o * feed['w']), o

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True))(
        feed['q'], feed['k'], feed['v'])
    return [np.asarray(a) for a in (out,) + grads]


def _dot_types(text):
    """(lhs dtype, rhs dtype, result dtype) of every dot in a lowered
    step's text."""
    import re
    return re.findall(
        r'stablehlo\.dot_general.*: \(tensor<[0-9x]*x(\w+)>, '
        r'tensor<[0-9x]*x(\w+)>\) -> tensor<[0-9x]*x(\w+)>', text)


@pytest.mark.parametrize('route,T,grads', [('pallas', 512, True),
                                           ('xla', 256, False)])
def test_flash_op_amp_lowers_bf16_operands(route, T, grads, amp, engage):
    """Under AMP the op is on the MXU path like mul/matmul: float32 q, k,
    v reach the kernels (engaged route: forward and merged backward) and
    the XLA reference (T < 512: its forward; jax's transpose of a dot
    that accumulates float32 takes the float32 cotangent as it comes)
    as bf16, and every dot takes bf16 operands and accumulates float32;
    with AMP off the same program lowers float32 dots only."""
    amp.set_amp(True)
    dots = _dot_types(_flash_op_run(T, lower_only=True, grads=grads))
    assert dots and set(dots) == {('bf16', 'bf16', 'f32')}, set(dots)
    amp.set_amp(False)
    dots = _dot_types(_flash_op_run(T, lower_only=True, grads=grads))
    assert dots and set(dots) == {('f32', 'f32', 'f32')}, set(dots)


@pytest.mark.parametrize('T', [512, 256], ids=['pallas', 'xla'])
def test_flash_op_amp_output_dtype(T, amp, engage):
    """bf16 out where activations flow bf16 (act_bf16), float32 where
    they do not (set_amp_act(False)); the gradients of float32 inputs
    come back float32 either way."""
    amp.set_amp(True)
    out = _flash_op_run(T, blocks=128)
    assert out[0].dtype == jnp.bfloat16
    assert [g.dtype for g in out[1:]] == [np.float32] * 3
    amp.set_amp_act(False)
    out = _flash_op_run(T, blocks=128)
    assert [a.dtype for a in out] == [np.float32] * 4


@pytest.mark.parametrize('T', [512, 256], ids=['pallas', 'xla'])
def test_flash_op_amp_off_is_bit_identical(T, amp, engage):
    """With AMP off the op hands q, k, v on as they arrive: output and
    gradients are, bit for bit, what flash_attention gives called
    directly on the same float32 inputs (the parent's op body)."""
    amp.set_amp(False)
    for a, b in zip(_flash_op_run(T),
                    _flash_op_direct(T, pk.flash_attention)):
        assert a.dtype == np.float32
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize('route,T,blocks', [
    ('pallas', 512, 128), ('pallas', 512, None), ('xla', 256, None)])
def test_flash_op_amp_matches_f32_reference(route, T, blocks, amp, engage):
    """Forward and the gradients of q, k, v through the op under AMP at
    D = 64 against the float32 attention_reference, each within 3e-2 of
    the reference's largest magnitude (chip_smoke.py's kernel rule)."""
    amp.set_amp(True)
    got = _flash_op_run(T, blocks=blocks)
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got,
                          _flash_op_direct(T, pk.attention_reference)):
        a = np.asarray(a, np.float32)
        assert np.isfinite(a).all(), name
        err = np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6)
        assert err < 3e-2, '%s (%s): %.3g' % (name, route, err)


def _gqa_program(T, heads, kv_heads, dh, window=None):
    """One flash_attention op with fewer KV heads than query heads on
    fed q [B, T, heads*dh], k, v [B, T, kv_heads*dh], and the gradients
    of sum(out * w) in q, k, v."""
    import paddle_tpu.fluid as fluid
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        q, w = [fluid.layers.data(name=n, shape=[T, heads * dh],
                                  dtype='float32') for n in 'qw']
        k, v = [fluid.layers.data(name=n, shape=[T, kv_heads * dh],
                                  dtype='float32') for n in 'kv']
        for x in (q, k, v):
            x.stop_gradient = False
        out = fluid.layers.flash_attention(
            q, k, v, num_heads=heads, causal=True, num_kv_heads=kv_heads,
            head_dim=dh, window=window)
        loss = fluid.layers.reduce_sum(fluid.layers.elementwise_mul(out, w))
        fetch = [out] + fluid.gradients(loss, [q, k, v])
    return main, startup, fetch


@pytest.mark.parametrize('route,T,heads,kv_heads,dh', [
    ('pallas', 512, 4, 2, 64), ('pallas', 512, 2, 1, 128),
    ('xla', 256, 4, 2, 64), ('xla', 512, 4, 1, 32)])
def test_flash_op_with_fewer_kv_heads(route, T, heads, kv_heads, dh, amp,
                                      engage):
    """num_kv_heads < num_heads on both routes (the engaged one through
    the interpreter): each KV head serves its run of query heads, and
    its gradient is the sum over them. Against plain attention with the
    KV heads repeated; a head size that is not D / num_heads is taken
    from head_dim."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import flash_counts
    amp.set_amp(False)
    rng = np.random.RandomState(4)
    B = _FLASH_OP_B
    feed = {'q': rng.randn(B, T, heads * dh), 'w': rng.randn(B, T, heads * dh),
            'k': rng.randn(B, T, kv_heads * dh),
            'v': rng.randn(B, T, kv_heads * dh)}
    feed = {n: x.astype('float32') for n, x in feed.items()}
    main, startup, fetch = _gqa_program(T, heads, kv_heads, dh)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        before = flash_counts(by=('route', 'kv_heads'))
        got = exe.run(main, feed=feed, fetch_list=fetch)
        after = flash_counts(by=('route', 'kv_heads'))
    assert after.get((route, str(kv_heads)), 0) \
        > before.get((route, str(kv_heads)), 0)

    def loss(q, k, v):
        rep = heads // kv_heads
        kh = jnp.repeat(k.reshape(B, T, kv_heads, dh), rep, axis=2)
        vh = jnp.repeat(v.reshape(B, T, kv_heads, dh), rep, axis=2)
        o = pk.attention_reference(q.reshape(B, T, heads, dh), kh, vh,
                                   causal=True).reshape(q.shape)
        return jnp.sum(o * feed['w']), o

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(
        *(jnp.asarray(feed[n]) for n in 'qkv'))
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, (out,) + grads):
        assert a.shape == b.shape, name
        err = np.max(np.abs(a - np.asarray(b))) / np.max(np.abs(b))
        assert err < 2e-5, '%s (%s): %.3g' % (name, route, err)



# ---- a window on the one attention op ---------------------------------------
# (H, D, block_q, block_k, T, window): a window narrower than a block, a
# block wide, and several (the tiles its lower edge cuts are whole under
# both masks at these block sizes); unequal blocks either way round; T
# that the asked-for block does not divide (_pick_block takes 128); then
# square blocks of 384 rows whose cut tiles go in 3 row chunks: a window
# a block wide (no tile between the diagonal's and the edge's), two
# blocks (full tiles between, dead ones below), and one that is no
# multiple of the block (cut tiles whole, offsets from the grid)
_WINDOW_CASES = [(2, 64, 128, 128, 512, 100), (2, 64, 128, 128, 512, 128),
                 (2, 64, 128, 128, 512, 300), (1, 128, 128, 256, 512, 200),
                 (1, 128, 256, 128, 512, 300), (1, 128, 256, 256, 640, 256),
                 (1, 128, 384, 384, 1536, 384), (2, 64, 384, 384, 1536, 768),
                 (1, 128, 384, 384, 1536, 500)]
_WINDOW_IDS = ['h%d-d%d-%dx%d-t%d-w%d' % c for c in _WINDOW_CASES]


def _window_operands(H, D, T, B=1, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(*shape).astype('float32'))
            for shape in [(B, T, H, D)] * 4 + [(B, H, T)]]


def _out_lse_grads(fn, q, k, v, w, wl):
    """(out, lse) of ``fn(q, k, v)`` and every gradient of sum(out * w)
    + sum(lse * wl)."""
    def loss(q, k, v):
        out, lse = fn(q, k, v)
        return jnp.sum(out * w) + jnp.sum(lse * wl), (out, lse)
    (_, outs), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True)(q, k, v)
    return outs + grads


@pytest.mark.parametrize('two_pass', [False, True], indirect=True,
                         ids=['merged', 'two-pass'])
@pytest.mark.parametrize('H,D,bq,bk,T,window', _WINDOW_CASES,
                         ids=_WINDOW_IDS)
def test_windowed_flash_matches_the_band_masked_reference(
        H, D, bq, bk, T, window, two_pass):
    """The windowed forward and every gradient (through the lse output
    too), merged and two-pass backward, through the interpreter against
    plain attention under the causal and the band mask."""
    q, k, v, w, wl = _window_operands(H, D, T)
    want = _out_lse_grads(
        lambda q, k, v: pk.attention_reference_with_lse(
            q, k, v, True, window=window), q, k, v, w, wl)
    got = _out_lse_grads(
        lambda q, k, v: pk.flash_attention_with_lse(
            q, k, v, True, bq, bk, True, window), q, k, v, w, wl)
    for name, a, b in zip(('out', 'lse', 'dq', 'dk', 'dv'), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)
    # and the band really is narrower than the triangle
    full = pk.attention_reference(q, k, v, True)
    assert float(jnp.max(jnp.abs(full - want[0]))) > 1e-2


@pytest.mark.parametrize('window', [1152, 4096])
def test_a_window_that_reaches_every_key_runs_the_causal_kernels(window):
    """window >= T masks nothing: the op's kernels are the causal ones
    themselves (no window reaches them), bit for bit, in the output and
    in every gradient."""
    H, D, T, b = 1, 128, 1152, 384
    q, k, v, w, wl = _window_operands(H, D, T, seed=3)
    assert pk.effective_window(window, T) is None
    assert pk.effective_window(T - 1, T) == T - 1
    causal = _out_lse_grads(lambda q, k, v: pk.flash_attention_with_lse(
        q, k, v, True, b, b, True), q, k, v, w, wl)
    windowed = _out_lse_grads(lambda q, k, v: pk.flash_attention_with_lse(
        q, k, v, True, b, b, True, window), q, k, v, w, wl)
    for a, c in zip(windowed, causal):
        assert np.array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError):
        pk.flash_attention(q, k, v, causal=False, window=128,
                           interpret=True)


def _tile_has_a_kept_pair(qi, kb, bq, bk, window):
    i = np.arange(qi * bq, (qi + 1) * bq)[:, None]
    j = np.arange(kb * bk, (kb + 1) * bk)[None, :]
    return bool(np.any((j <= i) & (i - j < window)))


@pytest.mark.parametrize('bq,bk,T,window', [
    (128, 128, 1024, 100), (128, 128, 1024, 128), (128, 128, 1024, 384),
    (128, 256, 1024, 200), (256, 128, 1024, 300), (128, 128, 1024, 129),
    (1024, 1024, 8192, 2048)])
def test_live_tile_tables_follow_the_band(bq, bk, T, window):
    """The index maps of the q-major and the kv-major sweeps under a
    window: over a block's steps they name every tile that holds a kept
    (query, key) pair and no tile that holds none (a dead step repeats
    a live block, so nothing is fetched for it). At the cell's shape, 21
    of the causal triangle's 36 tiles."""
    n_qb, n_kb = T // bq, T // bk
    kb_at = pk._live_kb(True, bq, bk, n_kb, window)
    qi_at = pk._live_qi(True, bq, bk, window, n_qb)
    live = {(qi, kb) for qi in range(n_qb) for kb in range(n_kb)
            if _tile_has_a_kept_pair(qi, kb, bq, bk, window)}
    q_major = {(qi, int(kb_at(qi, j))) for qi in range(n_qb)
               for j in range(n_kb)}
    kv_major = {(int(qi_at(kb, i)), kb) for kb in range(n_kb)
                for i in range(n_qb)}
    assert q_major == live
    assert kv_major == live
    causal = {(qi, kb) for qi in range(n_qb) for kb in range(n_kb)
              if kb * bk <= (qi + 1) * bq - 1}
    assert live < causal
    if T == 8192:
        assert (len(live), len(causal)) == (21, 36)


@pytest.mark.parametrize('dtype,T,window,plan,diag', [
    ('bfloat16', 8192, 2048, (1024, 1024), 'chunked2'),
    ('bfloat16', 8192, 1536, (1024, 1024), 'whole'),
    ('bfloat16', 2048, 1024, (1024, 1024), 'chunked2'),
    ('bfloat16', 2048, 4096, (2048, 2048), 'chunked4'),
    ('float32', 2048, 512, (512, 1024), 'whole')])
def test_flash_plan_blocks_under_a_window(dtype, T, window, plan, diag):
    """A window keeps flash_plan off the one 2048 x 2048 tile (a tile
    its edge cuts may have to run whole, which that tile cannot), unless
    it reaches every key; its cut tiles go in the diagonal's row chunks
    where the edge falls on tile corners and whole otherwise."""
    q = jnp.zeros((1, T, 4, 128), dtype)
    assert pk.flash_plan(q, interpret=True, window=window) == plan
    assert pk.flash_diag(plan, True, pk.effective_window(window, T)) == diag


@pytest.mark.parametrize('route,T,window', [
    ('pallas', 512, 200), ('pallas', 512, 4096), ('xla', 256, 100)])
def test_flash_op_window_with_fewer_kv_heads(route, T, window, amp, engage):
    """The op's ``window`` with num_kv_heads < num_heads on both routes
    (the engaged one through the interpreter): output and every
    gradient against plain attention under the band mask with the KV
    heads repeated; the lowering counter carries the window, '0' for
    one that reaches every key, and window_flash_counts() the windowed
    lowerings that took the kernels."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import flash_counts, window_flash_counts
    amp.set_amp(False)
    heads, kv_heads, dh, B = 4, 2, 64, _FLASH_OP_B
    rng = np.random.RandomState(4)
    feed = {'q': rng.randn(B, T, heads * dh), 'w': rng.randn(B, T, heads * dh),
            'k': rng.randn(B, T, kv_heads * dh),
            'v': rng.randn(B, T, kv_heads * dh)}
    feed = {n: x.astype('float32') for n, x in feed.items()}
    main, startup, fetch = _gqa_program(T, heads, kv_heads, dh, window)
    exe = fluid.Executor(fluid.CPUPlace())
    label = str(window if window < T else 0)
    with fluid.scope_guard(fluid.Scope()), _plan_blocks((128, 128)):
        exe.run(startup)
        before = flash_counts(by=('route', 'window')), window_flash_counts()
        got = exe.run(main, feed=feed, fetch_list=fetch)
        after = flash_counts(by=('route', 'window')), window_flash_counts()
    # counted once a trace, as the other labels are
    assert after[0].get((route, label), 0) \
        > before[0].get((route, label), 0)
    engaged = route == 'pallas' and window < T
    assert (after[1].get((label,), 0) > before[1].get((label,), 0)) \
        == engaged
    assert set(after[1]) - set(before[1]) <= {(label,)}

    def loss(q, k, v):
        rep = heads // kv_heads
        kh = jnp.repeat(k.reshape(B, T, kv_heads, dh), rep, axis=2)
        vh = jnp.repeat(v.reshape(B, T, kv_heads, dh), rep, axis=2)
        o = pk.attention_reference(q.reshape(B, T, heads, dh), kh, vh,
                                   causal=True, window=window) \
            .reshape(q.shape)
        return jnp.sum(o * feed['w']), o

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(
        *(jnp.asarray(feed[n]) for n in 'qkv'))
    for name, a, b in zip(('out', 'dq', 'dk', 'dv'), got, (out,) + grads):
        err = np.max(np.abs(a - np.asarray(b))) / np.max(np.abs(b))
        assert err < 2e-5, '%s (%s): %.3g' % (name, route, err)


def test_flash_layer_refuses_a_window_without_causal():
    import paddle_tpu.fluid as fluid
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.layers.data(name='x', shape=[64, 128], dtype='float32')
        with pytest.raises(ValueError):
            fluid.layers.flash_attention(x, x, x, num_heads=2, causal=False,
                                         window=16)
        with pytest.raises(ValueError):
            fluid.layers.flash_attention(x, x, x, num_heads=2, window=0)


def test_flash_plan_counts_a_long_row_for_its_scores(monkeypatch):
    """The engagement floor is in rows at T <= 1024 and in scores past
    it: B1 H4 T4096 (16Ki rows) has the scores of 64Ki rows at T 1024
    and engages; the same rows at T 1024 do not; what engaged before
    still does."""
    monkeypatch.setattr(pk, '_on_tpu', lambda: True)
    bf16 = jnp.bfloat16
    assert pk.flash_plan(jnp.zeros((1, 4096, 4, 128), bf16)) == (1024, 1024)
    assert pk.flash_plan(jnp.zeros((1, 2048, 4, 128), bf16)) is None
    assert pk.flash_plan(jnp.zeros((4, 1024, 4, 128), bf16)) is None
    assert pk.flash_plan(jnp.zeros((2, 2048, 32, 64), bf16)) == (2048, 2048)
    assert pk.flash_plan(jnp.zeros((8, 512, 16, 64), bf16)) is not None
    assert pk.flash_plan(jnp.zeros((4, 512, 16, 64), bf16)) is None


@pytest.mark.parametrize('amp_on,route,T,heads', [
    (True, 'pallas', 512, 4), (True, 'xla', 256, 4),
    (False, 'pallas', 512, 4), (False, 'xla', 256, 4),
    (True, 'xla', 512, 3), (False, 'xla', 512, 3)])
def test_flash_counts_one_per_op_lowering(amp_on, route, T, heads, amp,
                                          engage):
    """One lowering of a two-layer program counts two flash_attention
    lowerings under the route taken and the dtype the attention ran
    in, and none under any other label. An odd number of heads at
    D = 64 cannot pair up into 128-lane blocks and takes the XLA route
    at any length."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import flash_counts
    amp.set_amp(amp_on)
    main, startup, fetch = _flash_op_program(T, depth=2, grads=False,
                                             heads=heads)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        before = flash_counts()
        exe.lowered(main, feed=_flash_op_feed(T, heads=heads),
                    fetch_list=fetch)
        after = flash_counts()
    moved = {key: n - before.get(key, 0) for key, n in after.items()
             if n != before.get(key, 0)}
    assert moved == {(route, 'bf16' if amp_on else 'f32'): 2}


@pytest.mark.parametrize('T,blocks,causal,route,diag', [
    (768, (384, 384), True, 'pallas', 'chunked3'),
    (1536, (768, 768), True, 'pallas', 'chunked3'),
    (2048, (1024, 1024), True, 'pallas', 'chunked2'),
    (512, None, True, 'pallas', 'whole'),        # f32 default: 512 x 512
    (512, (256, 256), True, 'pallas', 'whole'),
    (512, (256, 512), True, 'pallas', 'whole'),
    (512, (512, 256), True, 'pallas', 'whole'),
    (768, (384, 384), False, 'pallas', 'none'),
    (256, None, True, 'xla', 'none')])
def test_flash_counts_name_the_diagonal_body(T, blocks, causal, route,
                                             diag, amp, engage):
    """The lowering counter says which body the plan's blocks give the
    tiles on the diagonal: row chunks where block_q == block_k holds
    more than one chunk (of 512 rows, or of the largest 128-row multiple
    below that which divides the block), the whole tile under the mask
    otherwise, none where nothing is masked or the kernels do not run.
    flash_counts() keeps its (route, dtype) keys and sums over that
    label; flash_counts(by=('diag',)) reads it."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.compiler.passes import flash_counts
    amp.set_amp(False)
    x = jnp.zeros((_FLASH_OP_B, T, _FLASH_OP_H, _FLASH_OP_DH))
    plan = pk.flash_plan(x, *(blocks or (None, None)), causal=causal)
    assert (plan is not None) == (route == 'pallas')
    assert pk.flash_diag(plan, causal) == diag
    main, startup, fetch = _flash_op_program(T, grads=False, causal=causal)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()), _plan_blocks(blocks):
        exe.run(startup)
        before = flash_counts(), flash_counts(by=('diag',))
        exe.lowered(main, feed=_flash_op_feed(T), fetch_list=fetch)
        after = flash_counts(), flash_counts(by=('diag',))
    moved = [{key: n - was.get(key, 0) for key, n in now.items()
              if n != was.get(key, 0)} for was, now in zip(before, after)]
    assert moved == [{(route, 'f32'): 1}, {(diag,): 1}]


@pytest.mark.parametrize('amp_on,T,causal,blocks', [
    (True, 2048, True, (2048, 2048)), (True, 4096, True, (1024, 1024)),
    (False, 2048, True, (512, 1024))],
    ids=['bf16-T2048', 'bf16-T4096', 'f32'])
def test_flash_op_lowers_with_flash_plans_blocks(amp_on, T, causal, blocks,
                                                 amp, monkeypatch):
    """The op names no blocks: what reaches the kernels is exactly
    flash_plan's choice for the operands the attention runs in (bf16
    under AMP), and nothing between the op and the kernels holds a
    second one."""
    import paddle_tpu.fluid as fluid
    got = []

    def kernels(q, k, v, causal, bq, bk, interpret, window=None):
        got.append((q.dtype.name, bq, bk))
        return pk.attention_reference_with_lse(q, k, v, causal)

    monkeypatch.setattr(pk, '_on_tpu', lambda: True)
    monkeypatch.setattr(pk, '_FLASH_MIN_ROWS', 0)
    monkeypatch.setattr(pk, '_flash_lse', kernels)
    amp.set_amp(amp_on)
    main, startup, fetch = _flash_op_program(T, grads=False, causal=causal)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.lowered(main, feed=_flash_op_feed(T), fetch_list=fetch)
    dtype = 'bfloat16' if amp_on else 'float32'
    q = jnp.zeros((_FLASH_OP_B, T, _FLASH_OP_H, _FLASH_OP_DH), dtype)
    assert pk.flash_plan(q, causal=causal) == blocks
    assert got == [(dtype,) + blocks]


@pytest.mark.parametrize('T,kernels', [
    (8192, ['_flash_dkvdq_kernel']),                    # 256 MiB of slab
    (16384, ['_flash_dkv_kernel', '_flash_dq_kernel'])],  # 1 GiB
    ids=['under-the-cap', 'over-the-cap'])
def test_flash_backward_route_follows_the_slab(T, kernels, monkeypatch):
    """The backward's route is decided from the dq slab's bytes and
    nothing else: merged while n_kb * B * T * H * dh of the slab's
    dtype is within _MERGED_BWD_MAX_SLAB_BYTES, the two passes beyond.
    Read off the kernel names in the step lowered for the TPU at
    H * dh = 2048 (nothing is compiled or run)."""
    import re
    monkeypatch.setattr(pk, '_on_tpu', lambda: True)
    x = jax.ShapeDtypeStruct((1, T, 32, 64), jnp.bfloat16)

    def backward(q, k, v, g):
        return jax.vjp(pk.flash_attention, q, k, v)[1](g)

    text = jax.jit(backward).trace(x, x, x, x).lower(
        lowering_platforms=('tpu',)).as_text()
    names = set(re.findall(r'kernel_name = "(\w+)"', text))
    assert sorted(names - {'_flash_kernel'}) == kernels


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (pjit, custom_vjp_call, cond branches), the Pallas kernels' bodies
    left out: what XLA is asked to do around the kernels."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == 'pallas_call':
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize('H,D', [(4, 64), (2, 128)])
def test_flash_engaged_path_has_no_transpose(H, D):
    """The engaged path reads q, k, v [B, T, H*D] as the projections
    wrote them and writes out, dq, dk, dv the same way: heads are
    addressed by the BlockSpecs. Forward: no transpose and no copy
    anywhere. Backward: the one array that changes its order is the lse
    cotangent, B*H*T float32; nothing of q's size does."""
    B, T = 2, 256
    x = jnp.zeros((B, T, H * D), jnp.float32)

    def attend(q, k, v):
        heads = (B, T, H, D)
        return pk.flash_attention(
            q.reshape(heads), k.reshape(heads), v.reshape(heads),
            block_q=128, block_k=128, interpret=True).reshape(q.shape)

    def moved(fn, *args):
        eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
        names = [e.primitive.name for e in eqns]
        return names, [e.invars[0].aval.shape for e in eqns
                       if e.primitive.name in ('transpose', 'copy')]

    names, shapes = moved(attend, x, x, x)
    assert names.count('pallas_call') == 1 and shapes == []
    names, shapes = moved(
        lambda q, k, v, g: jax.vjp(attend, q, k, v)[1](g), x, x, x, x)
    assert names.count('pallas_call') == 2      # forward, merged backward
    assert shapes == [(B, H, T)], shapes


# ---- the grouped matmul of the routed experts ------------------------------
_GROUPED_HELD = 4
# rows an expert owns, of 512 in 128-row tiles
_GROUPED_SPLITS = {
    'balanced': [128, 128, 128, 128],
    'one-takes-all': [0, 512, 0, 0],
    'an-expert-without-a-row': [200, 0, 56, 256],
    'ends-on-tile-boundaries': [256, 128, 0, 128],
    'ends-off-tile-boundaries': [1, 130, 254, 127],
    'three-experts-in-one-tile': [100, 10, 8, 394],
}
_GROUPED_ORDERS = {'wide': (128, 256), 'narrow': (256, 128)}


def _grouped_operands(split, order, seed=0):
    rng = np.random.RandomState(seed)
    K, N = _GROUPED_ORDERS[order]
    sizes = np.asarray(_GROUPED_SPLITS[split], np.int32)
    M = int(sizes.sum())
    return (rng.randn(M, K).astype('float32'),
            rng.randn(_GROUPED_HELD, K, N).astype('float32'),
            rng.randn(M, N).astype('float32'), sizes)


def _grouped_dense(rows, w, g, sizes):
    """The product, its data gradient and its weight gradient under
    sum(out * g), one expert after the other."""
    out, d_rows, d_w = [], [], []
    lo = 0
    for e, n in enumerate(sizes):
        r, ge = rows[lo:lo + n], g[lo:lo + n]
        out.append(r @ w[e])
        d_rows.append(ge @ w[e].T)
        d_w.append(r.T @ ge)
        lo += n
    return {'product': np.concatenate(out),
            'data-gradient': np.concatenate(d_rows),
            'weight-gradient': np.stack(d_w)}


def _grouped_pallas(rows, w, g, sizes, interpret=True):
    def loss(rows, w):
        out = pk.grouped_matmul(rows, w, jnp.asarray(sizes),
                                interpret=interpret)
        return jnp.sum(out * g), out
    (_, out), (d_rows, d_w) = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(jnp.asarray(rows),
                                             jnp.asarray(w))
    return {'product': np.asarray(out), 'data-gradient': np.asarray(d_rows),
            'weight-gradient': np.asarray(d_w)}


_GROUPED_RUNS = {}


def _grouped_case(split, order, blocks):
    """One run of the three kernels for the three uses' cases; with
    ``blocks`` 'blocked' a weight block is one 128 x 128 tile, so the
    dimension that is not contracted goes in two blocks."""
    if (split, order, blocks) not in _GROUPED_RUNS:
        operands = _grouped_operands(split, order)
        with pytest.MonkeyPatch.context() as patch:
            if blocks == 'blocked':
                patch.setattr(pk, '_GROUPED_BLOCK_BYTES', 128 * 128 * 4)
                K, N = _GROUPED_ORDERS[order]
                assert pk._grouped_block(N, K, jnp.float32) < max(K, N)
            _GROUPED_RUNS[split, order, blocks] = (
                _grouped_pallas(*operands), _grouped_dense(*operands))
    return _GROUPED_RUNS[split, order, blocks]


@pytest.mark.parametrize('use', ['product', 'data-gradient',
                                 'weight-gradient'])
@pytest.mark.parametrize('blocks', ['whole', 'blocked'])
@pytest.mark.parametrize('order', sorted(_GROUPED_ORDERS))
@pytest.mark.parametrize('split', sorted(_GROUPED_SPLITS))
def test_grouped_matmul_matches_a_dense_loop(split, order, blocks, use):
    """The Pallas grouped matmul through the interpreter, float32,
    against one dense product an expert: rows @ W[e], its gradient in
    the rows (against W[e] transposed, read as it lies) and in W
    (accumulated over an expert's row tiles)."""
    got, want = _grouped_case(split, order, blocks)
    assert got[use].dtype == np.float32
    np.testing.assert_allclose(got[use], want[use], rtol=2e-5, atol=2e-4)
    if use == 'weight-gradient':
        for e, n in enumerate(_GROUPED_SPLITS[split]):
            if n == 0:
                assert not got[use][e].any()        # exactly zero


def test_grouped_matmul_visits_cover_every_row_once():
    """The visit table: tiles + held - 1 visits whatever the split, in
    row order, an expert's visits consecutive and every expert visited;
    the visits' rows partition the rows, each inside its tile and
    inside its expert's group."""
    tm, tiles = 128, 4
    for split, sizes in sorted(_GROUPED_SPLITS.items()):
        tile, expert, lo, hi, live = (np.asarray(t) for t in (
            pk.grouped_visits(jnp.asarray(sizes, jnp.int32), tiles, tm)))
        assert len(tile) == tiles + _GROUPED_HELD - 1, split
        assert live == [tiles * tm]
        assert lo[0] == 0 and hi[-1] == tiles * tm
        assert (lo[1:] == hi[:-1]).all() and (lo <= hi).all()
        assert (np.diff(expert) >= 0).all() and (np.diff(tile) >= 0).all()
        assert sorted(set(expert)) == list(range(_GROUPED_HELD))
        ends = np.cumsum(sizes)
        for t, e, a, b in zip(tile, expert, lo, hi):
            assert 0 <= t < tiles
            if a < b:
                assert t * tm <= a and b <= (t + 1) * tm
                assert ends[e] - sizes[e] <= a and b <= ends[e]


def test_grouped_matmul_keeps_experts_apart_and_reads_nothing_unwritten():
    """What libtpu's ragged-dot taught PR 31 (a gradient of 1.4e8 from
    rows nobody wrote, every CPU test green): the interpreter fills
    every buffer a kernel has not written with NaN, and the rows and
    the cotangent of one expert are 1e30, which its neighbours in the
    same row tile multiply by zero. No NaN comes out, the other experts'
    rows and weight gradients are what they are without the poison, the
    poisoned expert's own are finite where 1e30 times a weight is, and
    the expert without a row gets exactly zero."""
    from jax.experimental.pallas import tpu as pltpu
    nan_filled = pltpu.InterpretParams(uninitialized_memory='nan')
    rows, w, g, sizes = _grouped_operands('an-expert-without-a-row', 'wide')
    clean = _grouped_pallas(rows, w, g, sizes, interpret=nan_filled)
    lo, hi = sizes[0], sizes[0] + sizes[1] + sizes[2]   # expert 2's rows
    assert hi - lo == sizes[2] and lo // 128 == 1 and hi == 256
    rows[lo:hi], g[lo:hi] = 1e30, 1e30
    got = _grouped_pallas(rows, w, g, sizes, interpret=nan_filled)
    for name in ('product', 'data-gradient', 'weight-gradient'):
        assert not np.isnan(clean[name]).any(), name
        assert not np.isnan(got[name]).any(), name
    others = np.r_[0:lo, hi:len(rows)]
    for name in ('product', 'data-gradient'):
        np.testing.assert_array_equal(got[name][others], clean[name][others])
        assert np.isfinite(got[name]).all()
    for e in (0, 3):
        np.testing.assert_array_equal(got['weight-gradient'][e],
                                      clean['weight-gradient'][e])
    assert not got['weight-gradient'][1].any()
    assert not clean['weight-gradient'][1].any()


# rows an expert owns where the groups end before the 512 rows do: the
# rows past them belong to no expert
_GROUPED_SHORT_SPLITS = {
    'no-row-routed': [0, 0, 0, 0],
    'pairs-end-inside-a-tile': [100, 0, 60, 40],
    'pairs-end-on-a-tile-edge': [128, 100, 0, 28],
    'pairs-fill-the-chunk': [200, 0, 56, 256],
}
_GROUPED_SHORT_RUNS = {}


def _grouped_short_case(split, poison, route):
    """grouped_matmul's three results on 512 rows whose groups are
    ``split``, the rows and the cotangent past the groups all ``poison``
    (NaN / 1e30), through the interpreter that fills what no kernel
    wrote with NaN or through ``lax.ragged_dot``; and the dense loop's
    over the rows that lie in a group, zeros past them."""
    if (split, poison, route) not in _GROUPED_SHORT_RUNS:
        from jax.experimental.pallas import tpu as pltpu
        rng = np.random.RandomState(1)
        K, N = _GROUPED_ORDERS['wide']
        sizes = np.asarray(_GROUPED_SHORT_SPLITS[split], np.int32)
        live, M = int(sizes.sum()), 512
        rows = rng.randn(M, K).astype('float32')
        g = rng.randn(M, N).astype('float32')
        w = rng.randn(_GROUPED_HELD, K, N).astype('float32')
        want = _grouped_dense(rows[:live], w, g[:live], sizes)
        for name, width in (('product', N), ('data-gradient', K)):
            want[name] = np.concatenate(
                [want[name].reshape(live, width),
                 np.zeros((M - live, width), 'float32')])
        rows[live:], g[live:] = poison, poison
        interpret = None if route == 'ragged_dot' else \
            pltpu.InterpretParams(uninitialized_memory='nan')
        out, vjp = jax.vjp(
            lambda rows, w: pk.grouped_matmul(
                rows, w, jnp.asarray(sizes), interpret=interpret),
            jnp.asarray(rows), jnp.asarray(w))
        d_rows, d_w = vjp(jnp.asarray(g))
        got = {'product': np.asarray(out), 'data-gradient': np.asarray(d_rows),
               'weight-gradient': np.asarray(d_w)}
        _GROUPED_SHORT_RUNS[split, poison, route] = got, want, live
    return _GROUPED_SHORT_RUNS[split, poison, route]


@pytest.mark.parametrize('use', ['product', 'data-gradient',
                                 'weight-gradient'])
@pytest.mark.parametrize('route', ['interpreter', 'ragged_dot'])
@pytest.mark.parametrize('poison', [float('nan'), 1e30], ids=['nan', '1e30'])
@pytest.mark.parametrize('split', sorted(_GROUPED_SHORT_SPLITS))
def test_grouped_matmul_rows_past_the_groups_are_exact_zeros(
        split, poison, route, use):
    """sum(sizes) <= M: a row past the groups belongs to no expert. Its
    product and its data gradient are exactly zero and it adds to no
    weight gradient, whatever it and its cotangent hold and whatever
    the kernels, which pass over its tile, left in memory there; the
    rows in a group read what the dense loop reads."""
    got, want, live = _grouped_short_case(split, poison, route)
    assert not np.isnan(got[use]).any()
    np.testing.assert_allclose(got[use], want[use], rtol=2e-5, atol=2e-4)
    if use != 'weight-gradient':
        assert not got[use][live:].any()             # exactly zero
    else:
        for e, n in enumerate(_GROUPED_SHORT_SPLITS[split]):
            assert n or not got[use][e].any()


@pytest.mark.parametrize('split', sorted(_GROUPED_SHORT_SPLITS)
                         + sorted(_GROUPED_SPLITS))
def test_grouped_matmul_skips_every_visit_without_a_live_row(split):
    """The kernels run a visit's product on its rows below ``live``
    only, where it has any. Every visit whose rows lie past the
    groups, and every visit at which a tile start and a group start
    coincide, has none; the visits that run hold every live row once,
    in as many row tiles as live_row_tiles counts; the others' rows are
    the rows past the groups, which a product zeroes; and a visit past
    the groups reads the tile of the last live row, so no block moves
    for it."""
    tm, tiles = 128, 4
    sizes = {**_GROUPED_SPLITS, **_GROUPED_SHORT_SPLITS}[split]
    tile, expert, lo, hi, live = (np.asarray(t) for t in pk.grouped_visits(
        jnp.asarray(sizes, jnp.int32), tiles, tm))
    assert live.shape == (1,) and live[0] == sum(sizes)
    live = int(live[0])
    assert len(tile) == tiles + _GROUPED_HELD - 1
    assert sorted(set(expert)) == list(range(_GROUPED_HELD))
    assert (np.diff(expert) >= 0).all() and (np.diff(tile) >= 0).all()
    assert lo[0] == 0 and hi[-1] == tiles * tm and (lo[1:] == hi[:-1]).all()
    mid = np.clip(live, lo, hi)
    runs = lo < mid                              # the kernels' condition
    assert (~runs == ((lo >= live) | (lo == hi))).all()

    def rows(starts, ends):
        return np.concatenate([np.arange(a, b) for a, b in
                               zip(starts, ends)] or [np.arange(0)])
    np.testing.assert_array_equal(rows(lo[runs], mid[runs]), np.arange(live))
    np.testing.assert_array_equal(rows(mid, hi), np.arange(live, tiles * tm))
    ends = np.cumsum(sizes)
    for t, e, a, b in zip(tile[runs], expert[runs], lo[runs], mid[runs]):
        assert t * tm <= a and b <= (t + 1) * tm
        assert ends[e] - sizes[e] <= a and b <= ends[e]
    src = np.asarray(pk._src_tile(tile, live, tm))
    assert (src[runs] == tile[runs]).all()
    assert (src[lo >= live] == max(live - 1, 0) // tm).all()
    assert (len(set(src[runs])), tiles) == pk.live_row_tiles(
        sizes, tiles * tm, tm)


@pytest.mark.parametrize('counts,rows,tm,want', [
    ([512] * 8, 8448, 128, (32, 66)),            # the trinity cell, balanced
    ([424, 627, 500, 512, 530, 498, 505, 505], 8448, 128, (33, 66)),
    ([176] * 8, 3072, 128, (11, 24)),            # the nemotron cell, balanced
    ([0] * 8, 3072, 128, (0, 24)),               # the first chunk always runs
    ([384] * 8, 3072, 128, (24, 24)),            # pairs fill the chunk
    ([1] + [0] * 7, 3072, 128, (1, 24)),
    ([1000, 1000, 1000, 73], 3072, 128, (25, 48)),   # one row overflows
    ([4096, 4096, 904, 904], 8448, None, (79, 132)),
], ids=['trinity', 'trinity-uneven', 'nemotron', 'empty', 'full', 'one-row',
        'one-row-over', 'second-chunk-partly-filled'])
def test_live_row_tiles_against_a_hand_count(counts, rows, tm, want):
    assert pk.live_row_tiles(counts, rows, tm) == want
    assert pk.live_row_tiles(np.asarray(counts, np.int32), rows, tm) == want


@pytest.mark.parametrize('dtype,K,N,rows,backend,interpret,plan', [
    ('bfloat16', 1024, 2688, 3072, 'tpu', None, (128, False)),
    ('float32', 1024, 2688, 3072, 'tpu', None, None),      # no AMP
    ('bfloat16', 1024, 2688, 3072, 'cpu', None, None),
    ('bfloat16', 12, 20, 3072, 'tpu', None, None),         # odd widths
    ('bfloat16', 1024, 2688, 3000, 'tpu', None, None),     # a ragged tile
    ('float32', 128, 256, 512, 'cpu', True, (128, True)),  # the tests
], ids=['chip-amp', 'chip-f32', 'cpu', 'odd-widths', 'odd-rows',
        'interpreter'])
def test_grouped_plan_engages_by_backend_dtype_and_shape(
        dtype, K, N, rows, backend, interpret, plan, monkeypatch):
    monkeypatch.setattr(pk, '_on_tpu', lambda: backend == 'tpu')
    got = pk.grouped_plan(jax.ShapeDtypeStruct((rows, K), dtype),
                          jax.ShapeDtypeStruct((8, K, N), dtype), interpret)
    assert got == plan


# ---- rows summed into their tokens and picked from them (routed experts) ---
@pytest.mark.parametrize('rule,dtype,L,chunk,n,backend,interpret,plan', [
    ('sum', 'float32', 2048, 8448, 8192, 'tpu', None, (256, 512, False)),
    ('sum', 'float32', 1024, 3072, 4096, 'tpu', None, (256, 1024, False)),
    ('sum', 'float32', 2048, 8448, 8192, 'cpu', None, None),
    ('sum', 'bfloat16', 2048, 8448, 8192, 'tpu', None, None),
    ('sum', 'float32', 24, 256, 74, 'tpu', None, None),
    ('sum', 'float32', 2048, 8320, 8192, 'tpu', None, None),
    ('sum', 'float32', 128, 256, 50000, 'tpu', None, None),
    ('sum', 'float32', 128, 256, 74, 'cpu', True, (256, 128, True)),
    ('gather', 'float32', 2048, 8448, 8192, 'tpu', None, (256, 512, False)),
    ('gather', 'float32', 1024, 3072, 4096, 'tpu', None, None),
    ('gather', 'float32', 2048, 8448, 8192, 'cpu', None, None),
    ('gather', 'bfloat16', 2048, 8448, 8192, 'tpu', None, None),
    ('gather', 'float32', 2048, 8320, 8192, 'tpu', None, None),
    ('gather', 'float32', 2048, 256, 50000, 'tpu', None, None),
    ('gather', 'float32', 2048, 256, 74, 'cpu', True, (256, 2048, True)),
    ('gather', 'float32', 128, 256, 74, 'cpu', True, None),
], ids=['trinity', 'nemotron', 'cpu', 'bf16-rows', 'odd-width',
        'odd-chunk', 'too-many-tokens', 'interpreter', 'gather-trinity',
        'gather-nemotron-narrow', 'gather-cpu', 'gather-bf16-rows',
        'gather-odd-chunk', 'gather-too-many-tokens', 'gather-interpreter',
        'gather-interpreter-narrow'])
def test_row_sum_plan_engages_by_backend_dtype_and_shape(
        rule, dtype, L, chunk, n, backend, interpret, plan, monkeypatch):
    """The sum's kernel takes float32 rows on a TPU backend, a width of
    whole 128-lane tiles and a chunk of whole row blocks; its column
    block is the widest whose [n, block] result stays under the VMEM
    budget (512 of 2048 lanes at 8192 tokens, all 1024 at 4096), and
    with none of 128 lanes under it XLA's scatter-add runs. The
    gather's kernel (row_gather_plan) takes the same rule and blocks,
    its [n, block] column block of the tokens held as the sum's result
    is, where the rows are at least 2048 lanes wide: at nemotron's 1024
    (and in the interpreter alike) ``jnp.take`` runs."""
    monkeypatch.setattr(pk, '_on_tpu', lambda: backend == 'tpu')
    rule = pk.row_sum_plan if rule == 'sum' else pk.row_gather_plan
    got = rule(jax.ShapeDtypeStruct((chunk, L), dtype), n, interpret)
    assert got == plan


@pytest.mark.parametrize('live', [0, 1, 15, 16, 17, 63, 64])
def test_row_sum_adds_the_live_rows_in_row_order(live, monkeypatch):
    """The kernel through the interpreter that fills what it has not
    written with NaN, in row blocks of 16: the rows below ``live`` added
    into their tokens one by one in row order, so a float32 loop in the
    same order reads the same bits, also where one token takes two rows
    in a row and where several held experts name one token; a token no
    row names reads zero, and the rows past ``live`` (NaN, tokens out of
    range) are never read."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(pk, '_ROW_SUM_BLOCK_ROWS', 16)
    rng = np.random.RandomState(live)
    chunk, L, n = 64, 256, 40
    tok = rng.randint(0, n, chunk).astype(np.int32)
    tok[1::7] = tok[0::7][:len(tok[1::7])]         # a token twice in a row
    y = rng.randn(chunk, L).astype(np.float32)
    want = np.zeros((n, L), np.float32)
    for r in range(live):
        want[tok[r]] += y[r]
    tok[live:], y[live:] = 10 ** 6, np.nan
    plan = pk.row_sum_plan(jax.ShapeDtypeStruct((chunk, L), jnp.float32), n,
                           pltpu.InterpretParams(uninitialized_memory='nan'))
    got = np.asarray(pk.row_sum(jnp.asarray(y), jnp.asarray(tok),
                                jnp.int32(live), n, plan, None))
    np.testing.assert_array_equal(got, want)
    assert not got[np.setdiff1d(np.arange(n), tok[:live])].any()


@pytest.mark.parametrize('live', [0, 1, 15, 16, 17, 63, 64])
def test_row_gather_copies_the_live_rows_from_their_tokens(
        live, monkeypatch):
    """The kernel through the interpreter that fills what it has not
    written with NaN, in row blocks of 16: below ``live`` row r is
    src[tok[r]] and past it a zero row, bit for bit what ``jnp.take``
    and the live mask give, with no live row at all, mid-block, at a
    block's edge and over the whole chunk, where one token takes two
    rows in a row and where several held experts name one token. The
    rows past ``live`` (tokens out of range) and the tokens no live row
    names (NaN) are never copied."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(pk, '_ROW_SUM_BLOCK_ROWS', 16)
    monkeypatch.setattr(pk, '_ROW_GATHER_MIN_LANES', 128)
    rng = np.random.RandomState(live)
    chunk, L, n = 64, 256, 40
    tok = rng.randint(0, n, chunk).astype(np.int32)
    tok[1::7] = tok[0::7][:len(tok[1::7])]         # a token twice in a row
    src = rng.randn(n, L).astype(np.float32)
    src[np.setdiff1d(np.arange(n), tok[:live])] = np.nan
    want = np.where(np.arange(chunk)[:, None] < live, src[tok], 0.0)
    tok[live:] = 10 ** 6
    src, tok = jnp.asarray(src), jnp.asarray(tok)
    plan = pk.row_gather_plan(jax.ShapeDtypeStruct((chunk, L), jnp.float32),
                              n, pltpu.InterpretParams(
                                  uninitialized_memory='nan'))
    got = pk.row_gather(src, tok, jnp.int32(live), n, plan)
    xla = pk.row_gather(src, tok, jnp.int32(live), n, None)
    assert plan is not None and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(xla))
