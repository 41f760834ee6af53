"""The whole ``xing4_0`` model, beside ``test_hyper_ops.py`` (whose helpers
and small configuration these cases take): builder shapes and refusals,
both losses and every gradient against ``benchmark/reference/xing4_0.py``
with the blocks mirrored or not, eight shares of a sparse layer adding up
through the post-mix, and ``Module.fit``: the fused window with two heads,
every metric computed inside it, three steps following the reference's;
why a fit leaves the window; named metrics on the per-batch path."""
import logging

import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import metric as metric_mod
from mxnet_tpu.ops.transformer import (HYPER_STATS, hyper_stat_names,
                                       moe_stat_names)

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    'hyper_ops_cases', os.path.join(REPO, 'tests', 'unittest',
                                    'test_hyper_ops.py'))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)
ref, builder, latent = ops.ref, ops.builder, ops.latent
CFG, MIX, T, d, n = ops.CFG, ops.MIX, ops.T, ops.d, ops.n
_rand, _close, op = ops._rand, ops._close, ops.op
_mixing, _metric, _model = ops._mixing, ops._metric, ops._model


# -- the whole model -------------------------------------------------------------------------

def test_builder_shapes_are_the_references():
    sym = builder.get_symbol(CFG)
    assert sym.list_outputs() == ['softmax_output', 'mtp_softmax_output']
    args, outs, auxs = sym.infer_shape(data=(2, T), softmax_label=(2, T))
    names = sym.list_arguments()
    assert len(names) == len(set(names))    # the shared leaves are one each
    shapes = dict(zip(names, args))
    want = ref.param_shapes(CFG)
    assert set(shapes) - {'data', 'softmax_label'} == set(want)
    assert all(tuple(shapes[k]) == tuple(s) for k, s in want.items())
    assert outs == [(2 * T, CFG['vocab_size'])] * 2
    aux = dict(zip(sym.list_auxiliary_states(), auxs))
    assert moe_stat_names(sym) == ['layer1_moe_stats', 'mtp_moe_stats']
    assert len(hyper_stat_names(sym)) == 6
    assert set(moe_stat_names(sym)) | set(hyper_stat_names(sym)) == set(aux)
    assert all(aux[k] == (len(HYPER_STATS),) for k in hyper_stat_names(sym))
    # every leaf has a rule in the benchmark's seeded initialisation
    assert all(k.endswith(('_weight', '_gamma')) and
               (len(s) >= 2 or k.endswith('_gamma')) for k, s in want.items())
    # without a prediction module: one head, the family's plain symbol
    plain = builder.get_symbol(dict(CFG, num_nextn_predict_layers=0))
    assert plain.list_outputs() == ['softmax_output']


@pytest.mark.parametrize('unbuilt', [
    dict(num_nextn_predict_layers=2), dict(n_group=8, topk_group=4),
    dict(rope_scaling={'type': 'linear', 'factor': 4}),
    dict(scoring_func='softmax')], ids=lambda v: sorted(v)[0])
def test_builder_refuses_what_it_does_not_build(unbuilt):
    with pytest.raises(ValueError, match='xing4_0|deepseek_v3'):
        builder.get_symbol(dict(CFG, **unbuilt))


def _bound(sym, p, tok, lab):
    ex = sym.simple_bind(mx.cpu(), data=tok.shape, softmax_label=lab.shape)
    for k, v in p.items():
        ex.arg_dict[k][:] = v
    ex.arg_dict['data'][:] = tok.astype(np.float32)
    ex.arg_dict['softmax_label'][:] = lab.astype(np.float32)
    return ex


def _losses(outs, lab):
    """(L_main, L_mtp) from the two outputs, the second aligned to the
    label rows with a uniform row 0."""
    B, L = lab.shape
    main = -np.log(outs[0][np.arange(B * L), lab.reshape(-1)]).mean()
    second = outs[1].reshape(B, L, -1)
    np.testing.assert_allclose(second[:, 0], 1.0 / second.shape[-1],
                               rtol=1e-6)
    mtp = -np.log(np.take_along_axis(second[:, 1:], lab[:, 1:, None],
                                     -1)).mean()
    return main, mtp


@pytest.mark.parametrize('remat', [True, False])
def test_model_losses_and_gradient(remat):
    cfg = dict(CFG, experts_held=8, expert_offset=4)
    sym = builder.get_symbol(cfg, remat=remat)
    p = _model(cfg, seed=1)
    rng = np.random.RandomState(1)
    tok, lab = rng.randint(0, 96, (2, T)), rng.randint(0, 96, (2, T))
    ex = _bound(sym, p, tok, lab)
    outs = [o.asnumpy() for o in ex.forward(is_train=True)]
    ex.backward()
    want_main, pairs, g, want_mtp = ref.loss_and_grad(
        {k: jnp.asarray(v) for k, v in p.items()}, tok, lab, cfg)
    main, mtp = _losses(outs, lab)
    assert abs(main - float(want_main)) < 1e-5
    assert abs(mtp - float(want_mtp)) < 1e-5
    for k in p:
        _close(ex.grad_dict[k].asnumpy(), g[k], tol=1e-4)
    # both heads reach the leaves they share
    for k in ('embed_weight', 'head_weight'):
        assert ex.grad_dict[k].asnumpy().any()
    got = [int(ex.aux_dict[k].asnumpy()[0]) for k in moe_stat_names(sym)]
    assert got == [int(v) for v in pairs]


def test_eight_shares_of_a_sparse_layer_add_up_through_the_post_mix():
    """model-configs guide, section 4: the expert sublayer's update summed
    over 8 shares of 2 experts, the shared expert counted once, then mixed
    in, is the uncut reference's sublayer."""
    cfg = dict(CFG, hc_sinkhorn_iters=20)
    whole = latent._moe_params(31, 16)
    p = _mixing(32)
    x = _rand(33, 1, T, n * d)
    y, coef, x_pass = op('HyperPre', **MIX)(
        x, p['s_hc_weight'], p['s_hc_bias_weight'], p['s_hc_alpha_gamma'],
        jnp.zeros((1,)))[:3]
    b = op('RMSNorm', eps=1e-6)(y, p['s_norm_gamma'])[0]
    shared = op('GatedMLP')(b, *latent._moe_weights(whole)[5:])
    total, pairs = -7 * shared, 0
    for share in range(8):
        part = dict(whole)
        for w in ('w1', 'w3', 'w2'):
            key = 'm_experts_%s_weight' % w
            part[key] = whole[key][2 * share:2 * share + 2]
        out, stats = latent._moe_op(2, 2 * share)(b, *latent._moe_weights(part))
        total, pairs = total + out, pairs + int(stats[0])
    got = op('HyperPost', n=n)(x_pass, total[None], coef)
    want = ref.sublayer(
        p, 's', x[0].reshape(T, n, d), cfg,
        lambda normed: ref.base.moe_layer(whole, 'm', normed, latent.CFG, 16,
                                          0)[0])
    _close(got[0], want.reshape(T, n * d))
    assert pairs == T * 3


# -- Module.fit ----------------------------------------------------------------------------

def test_fit_takes_the_fused_window_with_two_heads(monkeypatch):
    """No per-step dispatch and no silent None: the window is built, every
    metric is computed inside it, no output is stacked or fetched, and
    three steps follow the reference's."""
    steps, lr = 3, 0.05
    cfg = dict(CFG, experts_held=4)
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', str(steps))
    sym = builder.get_symbol(cfg)
    p = _model(cfg, seed=3)
    toks = np.random.RandomState(4).randint(0, 96, (steps, T + 1))
    it = mx.io.NDArrayIter(toks[:, :T].astype(np.float32),
                           toks[:, 1:].astype(np.float32), batch_size=1,
                           label_name='softmax_label')
    sums = []

    def note(param):
        ms = param.eval_metric.metrics
        sums.append((float(ms[0].sum_metric), float(ms[2].sum_metric),
                     int(ms[2].num_inst)))

    mod = mx.mod.Module(sym, context=mx.cpu())
    aux = {k: mx.nd.zeros(s) for k, s in zip(
        sym.list_auxiliary_states(),
        sym.infer_shape(data=(1, T), softmax_label=(1, T))[2])}
    mod.fit(it, eval_metric=_metric(), optimizer='sgd',
            optimizer_params={'learning_rate': lr, 'momentum': 0.9,
                              'wd': 0.0},
            arg_params={k: mx.nd.array(v) for k, v in p.items()},
            aux_params=aux, num_epoch=1, batch_end_callback=note)
    loop = mod.__dict__['_fused_fit_cache'][1]
    assert loop.window == steps and loop.stat_fns is not None
    assert len(loop.stat_fns) == 3
    # stats mode: what a step gives back is 3 x (sum, count), no output
    assert [type(c) for c in loop.children] == [
        metric_mod.CrossEntropy, metric_mod.Accuracy,
        metric_mod.CrossEntropy]
    w = {k: jnp.asarray(v) for k, v in p.items()}
    mom = {k: jnp.zeros_like(v) for k, v in w.items()}
    want_main, want_mtp = [], []
    for i in range(steps):
        main, _, g, mtp = ref.loss_and_grad(w, toks[i:i + 1, :T],
                                            toks[i:i + 1, 1:], cfg)
        want_main.append(float(main))
        want_mtp.append(float(mtp))
        w, mom = ref.sgd_momentum_step(w, mom, g, lr, 0.9)
    got = np.diff(np.asarray([(0.0, 0.0, 0)] + sums), axis=0)
    np.testing.assert_allclose(got[:, 0] / T, want_main, rtol=1e-4)
    assert (got[:, 2] == T).all()
    # the second head's metric over T rows, row 0 uniform
    first_row = -np.log(1.0 / 96 + 1e-12)
    np.testing.assert_allclose((got[:, 1] - first_row) / (T - 1), want_mtp,
                               rtol=1e-4)
    after = mod.get_params()[0]
    for k in p:
        _close(after[k].asnumpy() - p[k], np.asarray(w[k]) - p[k], tol=2e-3)


def test_fit_says_why_it_left_the_fused_window(monkeypatch, caplog):
    """Two heads whose metrics name no output, too large to stack: ``build``
    gives None and says why, once."""
    from mxnet_tpu.module import fused_fit
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', '64')
    monkeypatch.setattr(fused_fit, '_SAID', set())
    # 4 bytes x 64 steps x 2 outputs of (32, 20000): 0.33 GB to stack
    wide = dict(CFG, vocab_size=20000, num_hidden_layers=1,
                num_nextn_predict_layers=1)
    sym = builder.get_symbol(wide)
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[('data', (1, T))],
             label_shapes=[('softmax_label', (1, T))])
    mod.init_params()
    mod.init_optimizer(optimizer='sgd')
    with caplog.at_level(logging.WARNING):
        for _ in range(2):
            assert fused_fit.FusedFitLoop.build(
                mod, mx.metric.create(['ce', 'acc'])) is None
    said = [r.getMessage() for r in caplog.records
            if 'fused fit window not taken' in r.getMessage()]
    assert len(said) == 1 and 'names no output' in said[0]


def test_named_metrics_on_the_per_batch_path():
    """``update_metric`` hands a named metric the output it names."""
    m = _metric()
    lab = mx.nd.array(np.arange(8) % 5)
    pred = mx.nd.array(np.random.RandomState(0).dirichlet(np.ones(5), 8))
    uniform = mx.nd.array(np.full((8, 5), 0.2))
    m.update_dict({'softmax_label': lab},
                  {'softmax_output': pred, 'mtp_softmax_output': uniform})
    np.testing.assert_allclose(m.metrics[2].get()[1], -np.log(0.2 + 1e-12),
                               rtol=1e-6)
    assert abs(m.metrics[0].get()[1] - m.metrics[2].get()[1]) > 1e-3
