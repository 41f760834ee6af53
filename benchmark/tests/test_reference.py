"""The plain references against ``Module`` at a tiny size on the CPU, in
float32: training-mode forward, the gradient of every leaf, and the
inference-mode forward."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
os.environ['MXTPU_F16_AS_BF16'] = '1'

from benchmark import harness, weights  # noqa: E402
from benchmark.reference import convnets  # noqa: E402

CASES = [('resnet50_v1', 64, 4), ('inception_v3', 299, 2)]


@pytest.mark.parametrize('name,hw,batch', CASES)
def test_reference_follows_the_module(name, hw, batch):
    import jax.numpy as jnp
    import mxnet_tpu as mx
    cfg = harness.load_json(os.path.join(REPO, 'benchmark', 'configs',
                                         name + '.json'))
    kwargs = cfg['builder']['kwargs']
    kwargs['dtype'] = 'float32'
    if 'image_shape' in kwargs:
        kwargs['image_shape'] = '3,%d,%d' % (hw, hw)
    sym = harness.build_symbol(cfg)
    model = cfg['reference'].split(':')[1]
    dshape = (batch, 3, hw, hw)
    names, aux_names, both = harness.symbol_shapes(sym, batch, (3, hw, hw))
    shapes = {n: both[n] for n in names}
    made = {k: np.asarray(v) for k, v in weights.make_params(
        both, 7, cfg.get('init')).items()}
    rng = np.random.default_rng(0)
    x = rng.standard_normal(dshape, dtype=np.float32)
    y = np.arange(batch) * 3 % 10

    def module(train):
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[('data', dshape)],
                 label_shapes=[('softmax_label', (batch,))],
                 for_training=train)
        mod.init_params(
            arg_params={n: mx.nd.array(made[n]) for n in shapes},
            aux_params={n: mx.nd.array(made[n]) for n in aux_names})
        mod.forward(mx.io.DataBatch(
            data=[mx.nd.array(x)],
            label=[mx.nd.array(y.astype(np.float32))]), is_train=train)
        return mod

    params = {k: jnp.asarray(made[k]) for k in shapes}
    mod = module(True)
    mod.backward()
    got = np.log(mod.get_outputs()[0].asnumpy())
    want = np.asarray(convnets.log_probs(model, params, jnp.asarray(x), True))
    assert np.abs(got - want).max() < 2e-3
    _, grads = convnets.loss_and_grad(model, params, jnp.asarray(x),
                                      jnp.asarray(y), False)
    prog = dict(zip(mod._exec_group.param_names,
                    [g[0].asnumpy() for g in mod._exec_group.grad_arrays]))
    floor = float(np.median([np.linalg.norm(np.asarray(g))
                             for g in grads.values()]))
    for n in shapes:        # SoftmaxOutput's gradient is a sum over rows
        ref = float(np.linalg.norm(np.asarray(grads[n])))
        assert abs(np.linalg.norm(prog[n]) / batch - ref) \
            <= 0.03 * max(ref, floor), n
    got = np.log(module(False).get_outputs()[0].asnumpy())
    want = np.asarray(convnets.predict_log_probs(
        model, {k: jnp.asarray(v) for k, v in made.items()}, jnp.asarray(x)))
    assert np.abs(got - want).max() < 2e-3
