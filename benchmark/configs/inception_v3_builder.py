"""Builder of the ``inception_v3`` configuration: the Gluon model zoo's
Inception-v3 exported to a Symbol (as ``tests/unittest/test_gluon.py``
exports Gluon blocks), cast to float16 (bfloat16 under
``MXTPU_F16_AS_BF16``) after ``data`` and back to float32 before
``SoftmaxOutput``, like ``symbols/resnet.py``'s float16 mode.

The model zoo's dropout before the classifier is set to rate 0: the plain
reference cannot draw the program's mask, and a comparison of gradients
needs both sides to compute the same function.
"""


def get_symbol(classes=1000, dtype='float16'):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.get_model('inceptionv3', classes=classes,
                           prefix='inception3_')
    for block in net.features._children:
        if isinstance(block, nn.Dropout):
            block._rate = 0.0
    data = mx.sym.Variable('data')
    if dtype == 'float16':
        data = mx.sym.Cast(data=data, dtype='float16')
    out = net(data)
    if dtype == 'float16':
        out = mx.sym.Cast(data=out, dtype='float32')
    return mx.sym.SoftmaxOutput(data=out, name='softmax')
