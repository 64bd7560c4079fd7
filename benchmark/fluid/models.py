"""Benchmark model builders (parity: benchmark/fluid/{mnist,vgg,resnet,
se_resnext,stacked_dynamic_lstm,machine_translation}.py).

Each builder returns (avg_loss, feed_fn(batch_size) -> feed dict, unit).
Data is synthetic with fixed seed — the loop measures the training step,
not the input pipeline (which is benchmarked by the native loader tests).
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.lod import create_lod_tensor
from paddle_tpu.models import resnet as resnet_m
from paddle_tpu.models import vgg as vgg_m


def _img_feed(shape, classes):
    def feed_fn(bs):
        rng = np.random.RandomState(0)
        return {'data': rng.randn(bs, *shape).astype('float32'),
                'label': rng.randint(0, classes, (bs, 1)).astype('int64')}
    return feed_fn


def mnist(args):
    img = fluid.layers.data(name='data', shape=[1, 28, 28],
                            dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    conv1 = fluid.nets.simple_img_conv_pool(input=img, filter_size=5,
                                            num_filters=20, pool_size=2,
                                            pool_stride=2, act='relu')
    conv2 = fluid.nets.simple_img_conv_pool(input=conv1, filter_size=5,
                                            num_filters=50, pool_size=2,
                                            pool_stride=2, act='relu')
    predict = fluid.layers.fc(input=conv2, size=10, act='softmax')
    cost = fluid.layers.cross_entropy(input=predict, label=label)
    return (fluid.layers.mean(x=cost), _img_feed((1, 28, 28), 10),
            'images/sec')


def vgg(args):
    img = fluid.layers.data(name='data', shape=[3, 32, 32],
                            dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    predict = vgg_m.vgg16(img, class_dim=10)
    cost = fluid.layers.cross_entropy(input=predict, label=label)
    return (fluid.layers.mean(x=cost), _img_feed((3, 32, 32), 10),
            'images/sec')


def resnet(args):
    img = fluid.layers.data(name='data', shape=[3, 224, 224],
                            dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    predict = resnet_m.resnet_imagenet(img, class_dim=1000, depth=50)
    cost = fluid.layers.cross_entropy(input=predict, label=label)
    return (fluid.layers.mean(x=cost), _img_feed((3, 224, 224), 1000),
            'images/sec')


def se_resnext(args):
    img = fluid.layers.data(name='data', shape=[3, 224, 224],
                            dtype='float32')
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    predict = resnet_m.se_resnext(img, class_dim=1000, depth=50)
    cost = fluid.layers.cross_entropy(input=predict, label=label)
    return (fluid.layers.mean(x=cost), _img_feed((3, 224, 224), 1000),
            'images/sec')


def stacked_dynamic_lstm(args, use_peepholes=True):
    """Stacked LSTM sentiment net on synthetic word sequences
    (parity: benchmark/fluid/stacked_dynamic_lstm.py). The reference
    script keeps dynamic_lstm's default peepholes, which the fused
    Pallas cell does not take; ``use_peepholes=False`` is the variant
    that reaches it (chip_smoke.py runs both)."""
    dict_size = 10000
    emb_dim = 512
    hid_dim = 512
    stacked_num = 3
    seq_len = 80

    data = fluid.layers.data(name='data', shape=[1], dtype='int64',
                             lod_level=1)
    label = fluid.layers.data(name='label', shape=[1], dtype='int64')
    emb = fluid.layers.embedding(input=data, size=[dict_size, emb_dim])
    fc1 = fluid.layers.fc(input=emb, size=hid_dim * 4)
    lstm1, _ = fluid.layers.dynamic_lstm(input=fc1, size=hid_dim * 4,
                                         use_peepholes=use_peepholes)
    inputs = [fc1, lstm1]
    for _ in range(2, stacked_num + 1):
        fc = fluid.layers.fc(input=inputs, size=hid_dim * 4)
        lstm, _ = fluid.layers.dynamic_lstm(input=fc, size=hid_dim * 4,
                                            use_peepholes=use_peepholes)
        inputs = [fc, lstm]
    fc_last = fluid.layers.sequence_pool(input=inputs[0], pool_type='max')
    lstm_last = fluid.layers.sequence_pool(input=inputs[1], pool_type='max')
    prediction = fluid.layers.fc(input=[fc_last, lstm_last], size=2,
                                 act='softmax')
    cost = fluid.layers.cross_entropy(input=prediction, label=label)

    def feed_fn(bs):
        rng = np.random.RandomState(0)
        rows = rng.randint(0, dict_size, (bs * seq_len, 1)).astype('int64')
        st = create_lod_tensor(rows, [[seq_len] * bs])
        lab = rng.randint(0, 2, (bs, 1)).astype('int64')
        return {'data': st, 'label': lab}

    return fluid.layers.mean(x=cost), feed_fn, 'sequences/sec'


def machine_translation(args):
    """Seq2seq encoder-decoder with attention on synthetic parallel data
    (parity: benchmark/fluid/machine_translation.py)."""
    dict_size = 8000
    emb_dim = 256
    hid_dim = 512
    src_len, trg_len = 24, 24

    src = fluid.layers.data(name='data', shape=[1], dtype='int64',
                            lod_level=1)
    trg = fluid.layers.data(name='trg', shape=[1], dtype='int64',
                            lod_level=1)
    label = fluid.layers.data(name='label', shape=[1], dtype='int64',
                              lod_level=1)
    src_emb = fluid.layers.embedding(input=src, size=[dict_size, emb_dim])
    enc_fc = fluid.layers.fc(input=src_emb, size=hid_dim * 4)
    enc, _ = fluid.layers.dynamic_lstm(input=enc_fc, size=hid_dim * 4)
    enc_last = fluid.layers.sequence_pool(input=enc, pool_type='last')

    trg_emb = fluid.layers.embedding(input=trg, size=[dict_size, emb_dim])
    dec_fc = fluid.layers.fc(input=trg_emb, size=hid_dim * 4)
    dec, _ = fluid.layers.dynamic_lstm(input=dec_fc, size=hid_dim * 4)
    # context via last encoder state broadcast over decoder steps
    ctx = fluid.layers.sequence_expand(x=enc_last, y=dec)
    merged = fluid.layers.fc(input=[dec, ctx], size=hid_dim, act='tanh')
    predict = fluid.layers.fc(input=merged, size=dict_size, act='softmax')
    cost = fluid.layers.cross_entropy(input=predict, label=label)

    def feed_fn(bs):
        rng = np.random.RandomState(0)
        s_rows = rng.randint(0, dict_size,
                             (bs * src_len, 1)).astype('int64')
        t_rows = rng.randint(0, dict_size,
                             (bs * trg_len, 1)).astype('int64')
        l_rows = rng.randint(0, dict_size,
                             (bs * trg_len, 1)).astype('int64')
        return {'data': create_lod_tensor(s_rows, [[src_len] * bs]),
                'trg': create_lod_tensor(t_rows, [[trg_len] * bs]),
                'label': create_lod_tensor(l_rows, [[trg_len] * bs])}

    return fluid.layers.mean(x=cost), feed_fn, 'sentence_pairs/sec'


def transformer(args, vocab=8192, d_model=1024, n_heads=16, n_layers=6,
                d_ff=4096, seq=2048):
    """Decoder-only transformer LM through the FLUID surface: the
    flagship long-context path (layers.flash_attention -> Pallas kernel
    on TPU) built as a Program and run by the Executor, so the
    framework's lowering/executor is in the measured loop. Keyword dims
    exist for small-shape CPU tests."""
    tok = fluid.layers.data(name='data', shape=[seq], dtype='int64')
    label = fluid.layers.data(name='label', shape=[seq, 1], dtype='int64')
    pos = fluid.layers.data(name='pos', shape=[seq], dtype='int64')
    x = fluid.layers.embedding(input=tok, size=[vocab, d_model])
    p = fluid.layers.embedding(input=pos, size=[seq, d_model],
                               param_attr='pos_table')
    x = x + p
    for i in range(n_layers):
        ln = fluid.layers.layer_norm(x, begin_norm_axis=2)
        q = fluid.layers.fc(input=ln, size=d_model, num_flatten_dims=2,
                            bias_attr=False)
        k = fluid.layers.fc(input=ln, size=d_model, num_flatten_dims=2,
                            bias_attr=False)
        v = fluid.layers.fc(input=ln, size=d_model, num_flatten_dims=2,
                            bias_attr=False)
        att = fluid.layers.flash_attention(q, k, v, num_heads=n_heads,
                                           causal=True)
        proj = fluid.layers.fc(input=att, size=d_model,
                               num_flatten_dims=2, bias_attr=False)
        x = x + proj
        ln2 = fluid.layers.layer_norm(x, begin_norm_axis=2)
        ff = fluid.layers.fc(input=ln2, size=d_ff, num_flatten_dims=2,
                             act='relu')
        ff2 = fluid.layers.fc(input=ff, size=d_model, num_flatten_dims=2)
        x = x + ff2
    x = fluid.layers.layer_norm(x, begin_norm_axis=2)
    logits = fluid.layers.fc(input=x, size=vocab, num_flatten_dims=2)
    loss = fluid.layers.softmax_with_cross_entropy(logits=logits,
                                                   label=label)

    def feed_fn(bs):
        rng = np.random.RandomState(0)
        return {'data': rng.randint(0, vocab, (bs, seq)).astype('int64'),
                'label': rng.randint(0, vocab,
                                     (bs, seq, 1)).astype('int64'),
                'pos': np.tile(np.arange(seq, dtype='int64'), (bs, 1))}

    return fluid.layers.mean(x=loss), feed_fn, 'tokens/sec'


MODELS = {
    'mnist': mnist,
    'vgg': vgg,
    'resnet': resnet,
    'se_resnext': se_resnext,
    'stacked_dynamic_lstm': stacked_dynamic_lstm,
    'machine_translation': machine_translation,
    'transformer': transformer,
}
