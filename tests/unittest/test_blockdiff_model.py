"""The whole ``sdar_moe`` model trained as a block-diffusion model, beside
``test_blockdiff_ops.py`` (whose helpers and small configuration these
cases take): builder shapes and refusals, loss and every gradient against
``benchmark/reference/sdar_moe.py`` on both dispatch paths, three faults
that the comparison has to see (a plain causal mask, the weights dropped,
positions that run on to 2 L), the shares of the expert layer against the
uncut layer, ``Module.fit`` taking the fused window under the noising
iterator with its metric inside it and following the reference's steps,
and the benchmark's own files for this family."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.ops.transformer import MOE_STATS, moe_stat_names

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    'blockdiff_ops_cases', os.path.join(REPO, 'tests', 'unittest',
                                        'test_blockdiff_ops.py'))
ops = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ops)
ref, builder, noising, CFG, L = (ops.ref, ops.builder, ops.noising, ops.CFG,
                                 ops.L)
_close, _load, path, PATHS = ops._close, ops._load, ops.path, ops.PATHS
MASK_ID = ops.MASK_ID


def _model(cfg, seed=0):
    shapes = ref.param_shapes(cfg)
    rng = np.random.RandomState(seed)
    return {n: np.ones(s, np.float32) if n.endswith('gamma') else
            (rng.randn(*s) / np.sqrt(1 if n == 'embed_weight' else s[1]))
            .astype(np.float32) for n, s in shapes.items()}


def _step(seed, batch=2, length=L):
    """(data, label, weight) of one noised step."""
    x0 = np.random.RandomState(seed).randint(0, MASK_ID, (batch, length))
    mask, weight = noising.noise(seed, 0, batch, length, 4)
    return noising.noised(x0, mask, weight, MASK_ID)


# -- the whole model -------------------------------------------------------------------------

def test_builder_shapes_are_the_references():
    sym = builder.get_symbol(CFG, seq_len=L)
    assert sym.list_outputs() == ['softmax_output']
    args, outs, auxs = sym.infer_shape(**ops.BD_IN)
    shapes = dict(zip(sym.list_arguments(), args))
    want = ref.param_shapes(CFG)
    assert set(shapes) - set(ops.BD_IN) == set(want)
    assert all(tuple(shapes[k]) == tuple(s) for k, s in want.items())
    assert shapes['layer1_attn_q_norm_gamma'] == (16,)
    assert not any('shared' in k or 'select_bias' in k for k in shapes)
    # the head reads the noisy half alone
    assert outs == [(2 * L, CFG['vocab_size'])]
    assert moe_stat_names(sym) == sym.list_auxiliary_states() \
        == ['layer%d_moe_stats' % i for i in range(2)]
    assert auxs == [(len(MOE_STATS),)] * 2
    assert all(k.endswith(('_weight', '_gamma')) and
               (len(s) >= 2 or k.endswith('_gamma')) for k, s in want.items())


@pytest.mark.parametrize('unbuilt', [
    dict(decoder_sparse_step=2), dict(mlp_only_layers=[0]),
    dict(rope_scaling={'type': 'yarn', 'factor': 4}),
    dict(use_sliding_window=True), dict(tie_word_embeddings=True),
    dict(attention_bias=True)], ids=lambda v: sorted(v)[0])
def test_builder_refuses_what_it_does_not_build(unbuilt):
    with pytest.raises(ValueError, match='sdar_moe'):
        builder.get_symbol(dict(CFG, **unbuilt), seq_len=L)
    with pytest.raises(ValueError, match='seq_len'):
        builder.get_symbol(CFG)


def _bound(sym, p, data, label, weight):
    ex = sym.simple_bind(mx.cpu(), data=data.shape,
                         softmax_label=label.shape, loss_weight=weight.shape)
    for k, v in p.items():
        ex.arg_dict[k][:] = v
    ex.arg_dict['data'][:] = data
    ex.arg_dict['softmax_label'][:] = label
    ex.arg_dict['loss_weight'][:] = weight
    return ex


def _program(sym, p, data, label, weight):
    """(the mean cross-entropy over the masked rows, {leaf: gradient},
    the executor) of one step of `sym`."""
    ex = _bound(sym, p, data, label, weight)
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward()
    lab = label.reshape(-1)
    masked = lab != -1
    loss = -np.log(out[np.arange(lab.size)[masked],
                       lab[masked].astype(int)]).mean()
    return loss, {k: ex.grad_dict[k].asnumpy() for k in p}, ex


@pytest.mark.parametrize('path,remat', [
    ('plain', True), ('kernel', True), ('plain', False)], indirect=['path'])
def test_model_loss_and_gradient(path, remat):
    cfg = dict(CFG, experts_held=8, expert_offset=4)
    sym = builder.get_symbol(cfg, remat=remat, seq_len=L)
    p = _model(cfg, seed=1)
    data, label, weight = _step(1)
    loss, grads, ex = _program(sym, p, data, label, weight)
    w = {k: jnp.asarray(v) for k, v in p.items()}
    want, pairs, g = ref.loss_and_grad(w, data, label, weight, cfg)
    assert abs(loss - float(want)) < 1e-5
    for k in p:
        _close(grads[k], g[k], tol=1e-4)
    assert all(np.abs(v).max() > 0 for v in grads.values())
    got = [int(ex.aux_dict[k].asnumpy()[0]) for k in moe_stat_names(sym)]
    assert got == [int(v) for v in pairs]
    if not remat or path != 'plain':
        return
    # at_masters: the reference handed float32 masters computes with their
    # bfloat16 roundings and gives the gradient there
    ids, packed = jnp.asarray(data, jnp.int32), \
        jnp.asarray(ref.pack(label, weight))
    a = ref._loss_and_grad(ref.working_weights(w), ids, packed,
                           ref.hashable(cfg), False, False)
    b = ref._loss_and_grad(w, ids, packed, ref.hashable(cfg), False, True)
    assert float(a[0]) == float(b[0]) and float(b[3]) == 0.0
    for k in p:
        _close(b[2][k], a[2][k], tol=1e-6)


ONE_LAYER = dict(CFG, num_hidden_layers=1)


def _faulty(fault):
    """The builder's symbol with one thing wrong, through the ops' own
    attributes: what a program that got the mechanism wrong would run."""
    made = {'GroupedQueryAttention': mx.sym.GroupedQueryAttention,
            'RotaryEmbedding': mx.sym.RotaryEmbedding}

    def attention(**kw):
        if fault == 'causal':
            kw.pop('block_length')
        return made['GroupedQueryAttention'](**kw)

    def rotary(x, **kw):
        if fault == 'positions':
            kw.pop('period')
        return made['RotaryEmbedding'](x, **kw)

    mx.sym.GroupedQueryAttention, mx.sym.RotaryEmbedding = attention, rotary
    try:
        return builder.get_symbol(ONE_LAYER, seq_len=L)
    finally:
        mx.sym.GroupedQueryAttention = made['GroupedQueryAttention']
        mx.sym.RotaryEmbedding = made['RotaryEmbedding']


@pytest.mark.parametrize('fault', ['causal', 'weights', 'positions'])
def test_a_program_that_got_the_mechanism_wrong_fails(fault):
    """The mask computed as plain causal, the weights dropped, the
    positions left running to 2 L: each leaves the reference by far more
    than the comparison allows."""
    p = _model(ONE_LAYER, seed=2)
    data, label, weight = _step(2, batch=1)
    _, _, g = ref.loss_and_grad({k: jnp.asarray(v) for k, v in p.items()},
                                data, label, weight, ONE_LAYER)
    loss, grads, _ = _program(
        _faulty(fault), p, data, label,
        np.ones_like(weight) if fault == 'weights' else weight)
    num = sum(float(np.sum((grads[k] - np.asarray(g[k])) ** 2)) for k in p)
    den = sum(float(np.sum(np.asarray(g[k]) ** 2)) for k in p)
    assert np.sqrt(num / den) > 0.1     # the comparison's limit: 0.02
    sound = float(ref.loss_and_grad(
        {k: jnp.asarray(v) for k, v in p.items()}, data, label, weight,
        ONE_LAYER)[0])
    # the weights scale the gradient, not the reported loss
    assert (abs(loss - sound) < 1e-5) == (fault == 'weights')


def test_four_ranks_shares_add_up_to_the_uncut_layer():
    """Section 4 of the model-configs guide: 8 experts held by each of 4
    ranks of a 32-expert layer; what the ranks' expert layers add, summed,
    is the uncut reference's layer output."""
    cfg = dict(CFG, num_experts=32, num_experts_per_tok=4, experts_held=32)
    rng = np.random.RandomState(3)
    b = jnp.asarray(rng.randn(2 * L, 64).astype(np.float32))
    p = {k: jnp.asarray(v) for k, v in _model(cfg, seed=4).items()}
    whole, pairs = ref.moe_layer(p, 'layer0_moe', b, cfg, 32, 0)
    assert int(pairs) == 2 * L * 4
    from mxnet_tpu.ops import registry
    fn = registry.get('MoE').fn
    total, counted = 0.0, 0
    for rank in range(4):
        lo = 8 * rank
        attrs = dict(scoring='softmax', num_experts=32, experts_held=8,
                     expert_offset=lo, num_experts_per_tok=4,
                     norm_topk_prob=True, hidden=24, shared_hidden=0)
        part, stats = fn(attrs, b[None], p['layer0_moe_router_weight'],
                         p['layer0_moe_experts_w1_weight'][lo:lo + 8],
                         p['layer0_moe_experts_w3_weight'][lo:lo + 8],
                         p['layer0_moe_experts_w2_weight'][lo:lo + 8],
                         jnp.zeros((len(MOE_STATS),)))
        if rank == 0:
            _close(part[0], ref.moe_layer(p, 'layer0_moe', b, cfg, 8, 0)[0],
                   tol=1e-4)
        total = total + part[0]
        counted += int(stats[0])
    _close(total, whole, tol=1e-4)
    assert counted == int(pairs)


# -- Module.fit ----------------------------------------------------------------------------

@pytest.mark.parametrize('path', PATHS, indirect=True)
def test_fit_takes_the_fused_window_and_follows_the_reference(
        path, monkeypatch, tmp_path):
    """The window is built with ``Perplexity(ignore_label=-1)`` computed
    inside it and both label-side arrays carried through it, and three
    steps follow the reference's on the iterator's own noise."""
    steps, lr, seed = 3, 0.05, 6
    monkeypatch.setenv('MXTPU_FIT_STEPS_PER_CALL', str(steps))
    monkeypatch.setenv('MXTPU_TELEMETRY', '1')
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', str(tmp_path / 't.jsonl'))
    ops.cases._reload_telemetry()
    sym = builder.get_symbol(CFG, seq_len=L)
    p = _model(CFG, seed=5)
    x0 = np.random.RandomState(seed).randint(0, MASK_ID, (steps, L))
    it = noising.BlockDiffusionIter(
        mx.io.NDArrayIter(x0.astype(np.float32), None, batch_size=1), 4,
        MASK_ID, seed=seed)
    sums = []

    def note(param):
        m, = param.eval_metric.metrics
        sums.append((float(m.sum_metric), int(m.num_inst)))

    metric = mx.metric.CompositeEvalMetric()
    metric.add(mx.metric.Perplexity(ignore_label=-1,
                                    output_names=['softmax_output'],
                                    label_names=['softmax_label']))
    mod = mx.mod.Module(sym, context=mx.cpu(),
                        label_names=builder.LABEL_NAMES)
    try:
        mod.fit(it, eval_metric=metric, optimizer='sgd',
                optimizer_params={'learning_rate': lr, 'momentum': 0.9,
                                  'wd': 0.0},
                arg_params={k: mx.nd.array(v) for k, v in p.items()},
                aux_params={n: mx.nd.zeros((len(MOE_STATS),))
                            for n in sym.list_auxiliary_states()},
                num_epoch=1, batch_end_callback=note)
        counters = dict(telemetry.snapshot()['counters'])
        gauges = dict(telemetry.snapshot().get('gauges', {}))
    finally:
        monkeypatch.delenv('MXTPU_TELEMETRY')
        ops.cases._reload_telemetry()
    loop = mod.__dict__['_fused_fit_cache'][1]
    assert loop.window == steps and loop.stat_fns is not None
    w = {k: jnp.asarray(v) for k, v in p.items()}
    mom = {k: jnp.zeros_like(v) for k, v in w.items()}
    want, masked = [], []
    for i in range(steps):
        mask, weight = noising.noise(seed, i, 1, L, 4)
        data, label, weight = noising.noised(x0[i:i + 1], mask, weight,
                                             MASK_ID)
        loss, _, g = ref.loss_and_grad(w, data, label, weight, CFG)
        want.append(float(loss))
        masked.append(int(mask.sum()))
        w, mom = ref.sgd_momentum_step(w, mom, g, lr, 0.9)
    got = np.diff([(0.0, 0)] + sums, axis=0)
    assert [int(n) for n in got[:, 1]] == masked
    np.testing.assert_allclose(got[:, 0] / got[:, 1], want, rtol=1e-4)
    after = mod.get_params()[0]
    for n in p:
        _close(after[n].asnumpy() - p[n], np.asarray(w[n]) - p[n], tol=2e-3)
    # what the program counts: the rows that carried loss, the mask's
    # pairs and the pairs in the three kernel blocks the op's walk visits
    assert counters['fit.labelled_rows'] == sum(masked)
    assert gauges['attention.blockdiff.pairs_needed'] == 16 * 8 * 9
    assert gauges['attention.blockdiff.pairs_visited'] == 3 * L * L


# -- the benchmark's own files for this family ------------------------------------------------

FLOPS_CASES = ['test_the_masks_true_pairs',
               'test_required_flops_of_the_cut_model',
               'test_shares_of_the_required_operations',
               'test_attention_work_by_hand',
               'test_expert_least_time_by_hand']


@pytest.mark.parametrize('case', FLOPS_CASES)
def test_flops_blockdiff_against_a_count_by_hand(case):
    """The cases of ``benchmark/tests/test_flops_blockdiff.py``, which the
    tier-1 run does not collect."""
    cases = _load('benchmark/tests/test_flops_blockdiff.py',
                  'flops_blockdiff_cases')
    assert sorted(n for n in dir(cases) if n.startswith('test_')) \
        == sorted(FLOPS_CASES)
    getattr(cases, case)()


def _config():
    with open(os.path.join(REPO, 'benchmark', 'configs',
                           'sdar_30b_a3b_chat.json')) as f:
        return json.load(f)


def test_the_configuration_keeps_every_published_width():
    """Against the catalog entry's numbers, written out here: a key that
    differs is named in ``reduced`` and is no width."""
    cfg = _config()
    published = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act='silu', hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=32768, max_window_layers=48,
        mlp_only_layers=[], model_type='sdar_moe', moe_intermediate_size=768,
        norm_topk_prob=True, num_attention_heads=32, num_experts=128,
        num_experts_per_tok=8, num_hidden_layers=48, num_key_value_heads=4,
        rms_norm_eps=1e-6, rope_scaling=None, rope_theta=1000000,
        sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False, vocab_size=151936)
    differs = sorted(k for k, v in published.items() if cfg[k] != v)
    assert differs == ['num_hidden_layers', 'vocab_size']
    assert cfg['reduced'] == ['num_hidden_layers', 'experts_held',
                              'vocab_size']
    assert sorted(cfg['reduced_detail']) == sorted(cfg['reduced'])
    assert (cfg['num_hidden_layers'], cfg['experts_held'],
            cfg['vocab_size'], cfg['block_length']) == (5, 16, 18992, 4)
    # 551.0 M parameters, 6.61 GB at 12 bytes each
    count = sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values())
    assert abs(count / 1e6 - 551.0) < 0.06
    assert round(count * 12 / 1e9, 2) == 6.61
    assert '551.0 M' in cfg['deployment'] and '6.61 GB' in cfg['deployment']
    # the builder takes it as it stands
    sym = builder.get_symbol(cfg, **cfg['builder']['kwargs'])
    rows = 2 * cfg['builder']['kwargs']['seq_len']
    shapes = dict(zip(sym.list_arguments(), sym.infer_shape(
        data=(1, rows), softmax_label=(1, rows // 2),
        loss_weight=(1, rows // 2))[0]))
    assert {k: tuple(shapes[k]) for k in ref.param_shapes(cfg)} \
        == {k: tuple(s) for k, s in ref.param_shapes(cfg).items()}
    assert cfg['eval_metric'] == [
        {'metric': 'Perplexity', 'ignore_label': -1,
         'output': 'softmax_output', 'label': 'softmax_label'}]


def test_the_driver_binds_what_this_family_needs():
    """``fit_tokens_blockdiff``: ``fit_tokens_heads``'s run over steps of
    2 L rows, with the noising iterator, both label-side inputs, the
    ignoring metric and this family's kernel groups; what its ``cut``
    hands the reference unpacks to the step's three arrays."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import types
    from benchmark import compare_lm_training, data_lm
    from benchmark.drivers import (fit_tokens, fit_tokens_blockdiff,
                                   fit_tokens_heads)
    from benchmark.drivers.fit_tokens_ref import NEEDED, load_reference
    cfg = _config()
    loaded = load_reference(cfg)
    assert all(hasattr(loaded, n) for n in NEEDED)
    assert loaded.param_shapes(cfg) == ref.param_shapes(cfg)
    small = dict(CFG, builder={'kwargs': {'seq_len': L}})
    tr = {'block_length': 4, 'noise_t': '0.45-0.95', 'seq_len': L}
    ctx = types.SimpleNamespace(seed=11, config=small, traffic=tr)
    before = (fit_tokens_heads.LIMITS, fit_tokens_heads.KERNEL_GROUPS,
              fit_tokens_heads.make_metric, fit_tokens.symbol_shapes,
              fit_tokens.make_iter, mx.mod.Module)
    try:
        made = fit_tokens_blockdiff.bind(ctx)
        assert sorted(fit_tokens_heads.LIMITS) \
            == sorted(compare_lm_training.LIMITS)
        seconds = fit_tokens_heads.kernel_seconds(
            {'attention_blockdiff_fwd.3 bf16': 1.0,
             'attention_blockdiff_bwd.1 (bf16, bf16, bf16)': 2.0,
             'attention_full_bwd.2 bf16': 4.0,
             'moe_expert_matmul_dw.7 f32': 8.0,
             'fusion.attention_blockdiff_fwd': 16.0}, 31.0)
        assert seconds == {'attention_blockdiff': 3.0, 'moe_expert': 8.0,
                           'busy': 31.0}
        sym = builder.get_symbol(CFG, seq_len=L)
        names, aux, shapes = fit_tokens.symbol_shapes(sym, 1, 2 * L)
        assert set(names) == set(ref.param_shapes(CFG))
        mod = mx.mod.Module(sym, context=mx.cpu())
        assert mod._label_names == builder.LABEL_NAMES
        metric, main, second = fit_tokens_heads.make_metric(
            mx, dict(eval_metric=cfg['eval_metric']))
        assert (main, second) == (0, None)
        assert metric.metrics[0].ignore_label == -1
        pool = data_lm.token_pool(11, 2048, 96)
        it = fit_tokens.make_iter(mx, pool, 1, 2 * L, 4)
        assert made == [it]
        it.plan(windows=1)
        batch = it.next()
        data, packed = it.cut(0)
        np.testing.assert_array_equal(batch.data[0].asnumpy(), data)
        label, weight = ref.unpack(jnp.asarray(packed))
        np.testing.assert_array_equal(batch.label[0].asnumpy(), label)
        np.testing.assert_array_equal(batch.label[1].asnumpy(), weight)
        assert data.shape == packed.shape == (1, 2 * L)
        assert MASK_ID not in data[:, L:] and (data[:, :L] == MASK_ID).any()
    finally:
        (fit_tokens_heads.LIMITS, fit_tokens_heads.KERNEL_GROUPS,
         fit_tokens_heads.make_metric, fit_tokens.symbol_shapes,
         fit_tokens.make_iter, mx.mod.Module) = before


def test_a_traced_slice_of_two_periods_is_counted_as_two(monkeypatch):
    """``fit_tokens_blockdiff.whole_periods``: the slice's steps and pairs
    follow its length over the run's own period; a slice of one period is
    left as ``fit_tokens_heads`` counted it."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    import types
    from benchmark.drivers import fit_tokens, fit_tokens_blockdiff
    events = [{'pairs': [[k]]} for k in (0, 0, 1, 10, 100, 1000)]
    monkeypatch.setattr(fit_tokens, 'read_events', lambda path, name: events)
    monkeypatch.setenv('MXTPU_TELEMETRY_PATH', 'unread')
    ctx = types.SimpleNamespace(log=lambda msg: None)

    def counted(window_s):
        run = {'batch': 1, 'samples_s': 32 * 8192 / 7.5, 'windows': 4,
               'trace': {'window_s': window_s, 'busy_s': window_s},
               'trace_steps': 32, 'moe_pairs_traced': 100}
        fit_tokens_blockdiff.whole_periods(ctx, run, 32, 8192)
        return run['trace_steps'], run['moe_pairs_traced']

    assert counted(7.49) == (32, 100)
    assert counted(14.99) == (64, 110)      # windows 2 and 3 of 1..4
