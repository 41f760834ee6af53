"""Convergence gate: MLP + conv accuracy thresholds.

Reference: tests/python/train/test_mlp.py + test_conv.py — train a small
net on MNIST for a couple of epochs and assert an accuracy floor. Runs
hermetically on the synthetic MNIST (io.MNISTIter falls back to
class-separable prototypes when the idx files are absent), same
train/eval protocol.
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx


pytestmark = pytest.mark.convergence

def _mnist_iters(batch_size=100, flat=False):
    train = mx.io.MNISTIter(image='train-images-idx3-ubyte',
                            label='train-labels-idx1-ubyte',
                            batch_size=batch_size, shuffle=True, flat=flat,
                            seed=1)
    val = mx.io.MNISTIter(image='t10k-images-idx3-ubyte',
                          label='t10k-labels-idx1-ubyte',
                          batch_size=batch_size, shuffle=False, flat=flat,
                          seed=2)
    return train, val


def _mlp_symbol():
    data = mx.sym.Variable('data')
    net = mx.sym.FullyConnected(data, name='fc1', num_hidden=64)
    net = mx.sym.Activation(net, name='relu1', act_type='relu')
    net = mx.sym.FullyConnected(net, name='fc2', num_hidden=32)
    net = mx.sym.Activation(net, name='relu2', act_type='relu')
    net = mx.sym.FullyConnected(net, name='fc3', num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _lenet_symbol():
    data = mx.sym.Variable('data')
    net = mx.sym.Convolution(data, name='conv1', kernel=(5, 5), num_filter=8)
    net = mx.sym.Activation(net, name='act1', act_type='tanh')
    net = mx.sym.Pooling(net, name='pool1', pool_type='max', kernel=(2, 2),
                         stride=(2, 2))
    net = mx.sym.Convolution(net, name='conv2', kernel=(5, 5), num_filter=16)
    net = mx.sym.Activation(net, name='act2', act_type='tanh')
    net = mx.sym.Pooling(net, name='pool2', pool_type='max', kernel=(2, 2),
                         stride=(2, 2))
    net = mx.sym.Flatten(net, name='flatten')
    net = mx.sym.FullyConnected(net, name='fc1', num_hidden=32)
    net = mx.sym.Activation(net, name='act3', act_type='tanh')
    net = mx.sym.FullyConnected(net, name='fc2', num_hidden=10)
    return mx.sym.SoftmaxOutput(net, name='softmax')


def _fit_and_score(sym, train, val, num_epoch, optimizer_params, flat):
    mx.random.seed(7)   # deterministic init regardless of suite order
    mod = mx.module.Module(sym, context=mx.current_context())
    mod.fit(train, eval_data=val, num_epoch=num_epoch,
            optimizer='sgd', optimizer_params=optimizer_params,
            initializer=mx.init.Xavier(),
            batch_end_callback=None, eval_metric='acc')
    score = mod.score(val, mx.metric.Accuracy())
    return dict(score)['accuracy']


@pytest.mark.slow
def test_mlp_convergence():
    train, val = _mnist_iters(flat=True)
    acc = _fit_and_score(_mlp_symbol(), train, val, num_epoch=3,
                         optimizer_params={'learning_rate': 0.1,
                                           'momentum': 0.9}, flat=True)
    logging.info('mlp accuracy: %.4f', acc)
    # reference test_mlp.py asserts 0.96 on real MNIST after 10 epochs;
    # the synthetic set is easier, so hold a higher bar in fewer epochs
    assert acc > 0.95, 'MLP failed to converge: acc=%.4f' % acc


@pytest.mark.slow
def test_lenet_convergence():
    train, val = _mnist_iters(batch_size=100, flat=False)
    acc = _fit_and_score(_lenet_symbol(), train, val, num_epoch=2,
                         optimizer_params={'learning_rate': 0.05,
                                           'momentum': 0.9}, flat=False)
    logging.info('lenet accuracy: %.4f', acc)
    assert acc > 0.95, 'LeNet failed to converge: acc=%.4f' % acc


@pytest.mark.slow
def test_gluon_mlp_convergence():
    """Same gate through the imperative frontend (reference test pattern:
    gluon mnist example)."""
    from mxnet_tpu import gluon
    import mxnet_tpu.autograd as ag
    from mxnet_tpu import nd

    train, _ = _mnist_iters(batch_size=100, flat=True)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(64, activation='relu'))
    net.add(gluon.nn.Dense(10))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': 1e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    last_losses = []
    for epoch in range(2):
        train.reset()
        for batch in train:
            data = batch.data[0]
            label = batch.label[0]
            with ag.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            last_losses.append(float(loss.mean().asnumpy()))
    # train accuracy
    train.reset()
    correct = total = 0
    for batch in train:
        out = net(batch.data[0])
        pred = out.asnumpy().argmax(1)
        correct += (pred == batch.label[0].asnumpy()).sum()
        total += pred.shape[0]
    acc = correct / total
    assert acc > 0.95, 'gluon MLP failed to converge: acc=%.4f' % acc
