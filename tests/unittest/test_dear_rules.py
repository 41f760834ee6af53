"""The rules by which ops name values for a mirrored stage
(``ops.registry.dear``), where they need no decoder to be seen:

- ``FullyConnected`` names its output where it contracts (4 -> 2 and 4 -> 4
  features) and not where it expands (2 -> 4); a mirrored stage keeps the
  named value and counts it once;
- the gated MLP names its two hidden products, as the float32 results of
  the products and before ``silu``, under both of its callers (``GatedMLP``
  and ``MoE``'s shared expert), and nothing else of itself;
- outside a stage a name is nothing: the fused window of a small residual
  network with a contracting head lowers to the text it had before the rule;
- the router's ``_top_k`` gives ``jax.lax.top_k``'s values, indices and
  gradient bit for bit.
"""
import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import random as _random
from mxnet_tpu.ops import registry
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.transformer import MOE_STATS, _top_k


def _fc(features_in, features_out, bias):
    fn = registry.get('FullyConnected').fn
    rng = np.random.RandomState(features_in * 8 + features_out)
    args = [jnp.asarray(rng.randn(3, features_in), jnp.float32),
            jnp.asarray(rng.randn(features_out, features_in), jnp.float32)]
    if bias:
        args.append(jnp.asarray(rng.randn(features_out), jnp.float32))
    return (lambda *a: fn({'num_hidden': features_out,
                           'no_bias': not bias}, *a)), args


@pytest.mark.parametrize('features,named', [
    ((4, 2), True), ((4, 4), True), ((2, 4), False)],
    ids=['4_to_2', '4_to_4', '2_to_4'])
@pytest.mark.parametrize('bias', [False, True], ids=['no_bias', 'bias'])
def test_fully_connected_names_its_output_where_it_contracts(
        features, named, bias):
    f, args = _fc(*features, bias)
    assert ('name[name=fully_connected_out]'
            in str(jax.make_jaxpr(f)(*args))) == named
    # in a mirrored stage the named output (the sum with the bias, where
    # there is one) is kept, once; outside it changes no value
    kept = []
    out, pull = jax.vjp(registry.mirrored(lambda *a: jnp.sin(f(*a)), kept),
                        *args)
    assert [k.shape for k in kept] == ([(3, features[1])] if named else [])
    plain, plain_pull = jax.vjp(
        lambda x, w, *b: jnp.sin(x @ w.T + (b[0] if b else 0.0)), *args)
    np.testing.assert_allclose(out, plain, rtol=1e-6)
    for a, b in zip(pull(jnp.ones_like(out)),
                    plain_pull(jnp.ones_like(out))):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


ROWS, WIDTH, HIDDEN = 6, 8, 16


def _mlp_caller(caller):
    """(f, arguments): the gated MLP at 8 -> 16 -> 8 over 6 bfloat16 rows,
    as the ``GatedMLP`` op or as the shared expert of a ``MoE`` layer."""
    rng = np.random.RandomState(3)

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.3, jnp.bfloat16)

    x = rand(ROWS, WIDTH)
    mlp = [rand(HIDDEN, WIDTH), rand(HIDDEN, WIDTH), rand(WIDTH, HIDDEN)]
    if caller == 'gated_mlp':
        fn = registry.get('GatedMLP').fn
        return (lambda x, *w: fn({}, x, *w)), [x] + mlp
    fn = registry.get('MoE').fn
    attrs = dict(num_experts=4, num_experts_per_tok=2, experts_held=4,
                 expert_offset=0, norm_topk_prob=True)
    experts = [rand(4, WIDTH), rand(4, WIDTH, 4), rand(4, WIDTH, 4),
               rand(4, 4, WIDTH)]
    stats = jnp.zeros((len(MOE_STATS),), jnp.float32)
    return (lambda x, *w: fn(attrs, x, *w, stats)[0]), [x] + experts + mlp


@pytest.mark.parametrize('caller', ['gated_mlp', 'moe_shared'])
def test_gated_mlp_names_its_two_hidden_products(caller, monkeypatch):
    f, args = _mlp_caller(caller)
    named = [(e.params['name'], e.outvars[0].aval)
             for e in jax.make_jaxpr(f)(*args).jaxpr.eqns
             if e.primitive.name == 'name'
             and e.params['name'].startswith('mlp_')]
    # g = x W1^T and u = x W3^T as `_matmul` gives them: not silu(g) u, not
    # the input, not the result
    assert [n for n, _ in named] == ['mlp_gate', 'mlp_up']
    assert all(a.shape == (ROWS, HIDDEN) and a.dtype == jnp.float32
               for _, a in named)
    # a mirrored stage keeps the two, once each, and what it gives is what
    # the bare checkpoint gives, bit for bit
    kept = []
    out, pull = jax.vjp(registry.mirrored(f, kept), *args)
    hidden = [k for k in kept if k.shape == (ROWS, HIDDEN)]
    assert len(hidden) == 2 and all(k.dtype == jnp.float32 for k in hidden)
    if caller == 'gated_mlp':
        assert len(kept) == 2
    bare, bare_pull = jax.vjp(jax.checkpoint(f), *args)
    for a, b in zip((out,) + pull(jnp.ones_like(out)),
                    (bare,) + bare_pull(jnp.ones_like(bare))):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    # outside a stage the lowered text is the unnamed function's
    text = jax.jit(f).lower(*args).as_text()
    for module in (mx.ops.transformer, pk):
        monkeypatch.setattr(module, 'dear', lambda x, name: x)
    f, args = _mlp_caller(caller)
    assert 'name[' not in str(jax.make_jaxpr(f)(*args))
    unnamed = jax.jit(f).lower(*args).as_text()
    text, unnamed = (re.sub(r'(@\w+?)_\d+\b', r'\1', t)
                     for t in (text, unnamed))
    assert unnamed == text


# sha256 of the lowered fused window of the network below, taken under
# pytest on the commit before ``FullyConnected`` named anything (9a31ec0):
# its head contracts (8 -> 4), lies in no stage, and lowers as it did.
# The text is this jax's.
WINDOW_TEXT = \
    '69feec6522e2e12ff59a720a0a35fe1280377f378c14cfe9908dbe558cb1789d'


def _residual_net():
    x = mx.sym.Convolution(mx.sym.Variable('data'), kernel=(3, 3),
                           num_filter=8, pad=(1, 1), name='c1')
    x = mx.sym.Activation(mx.sym.BatchNorm(x, name='bn1'), act_type='relu')
    y = mx.sym.Convolution(x, kernel=(3, 3), num_filter=8, pad=(1, 1),
                           name='c2')
    x = x + mx.sym.BatchNorm(y, name='bn2')
    x = mx.sym.Pooling(x, global_pool=True, pool_type='avg', kernel=(1, 1))
    x = mx.sym.FullyConnected(mx.sym.Flatten(x), num_hidden=4, name='fc')
    return mx.sym.SoftmaxOutput(x, name='softmax')


def test_a_name_outside_a_stage_lowers_to_nothing(monkeypatch):
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', '2')
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.randn(8, 3, 6, 6).astype(np.float32),
                           rng.randint(0, 4, (8,)).astype(np.float32),
                           batch_size=4)
    mod = mx.mod.Module(_residual_net(), context=mx.cpu())
    mod.fit(it, optimizer='sgd', num_epoch=1,
            optimizer_params={'learning_rate': 0.1, 'momentum': 0.9})
    loop = mod.__dict__['_fused_fit_cache'][1]
    fn = loop._build_program(loop._static_attrs(), None)
    params, states, aux, gaccs = loop._snapshot()
    lr, wd = loop._sample_window_lr()
    text = fn.lower(
        params, states, aux, gaccs,
        (jnp.zeros((loop.window, 4, 3, 6, 6), jnp.float32),),
        (jnp.zeros((loop.window, 4), jnp.float32),), _random.next_key(),
        lr, wd).as_text()
    assert 'name' not in re.findall(r'stablehlo\.(\w+)', text)
    # the counter behind the private functions' names is the process's
    text = re.sub(r'(@\w+?)_\d+\b', r'\1', text)
    assert hashlib.sha256(text.encode()).hexdigest() == WINDOW_TEXT


def test_top_k_is_jax_top_k_with_its_gradient():
    x = jnp.asarray(np.random.RandomState(5).rand(64, 16), jnp.float32)
    g = jnp.asarray(np.random.RandomState(6).randn(64, 3), jnp.float32)

    def pulled(top_k):
        (w, idx), pull = jax.vjp(lambda x: tuple(top_k(x, 3)), x)
        zero = np.zeros(idx.shape, jax.dtypes.float0)
        return w, idx, pull((g, zero))[0]

    for a, b in zip(pulled(_top_k), pulled(jax.lax.top_k)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # under jit, as a traced program has it
    a = jax.jit(jax.grad(lambda x: jnp.sum(_top_k(x, 3)[0] * g)))(x)
    b = jax.jit(jax.grad(lambda x: jnp.sum(jax.lax.top_k(x, 3)[0] * g)))(x)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
