"""Decoders of the ``xing4_0`` family as a Symbol for ``Module.fit``: the
``deepseek_v3`` blocks (latent attention with a low-rank query path and
YaRN rotary scaling, a sigmoid router with a selection bias, a shared
expert: ``deepseek_v3.py`` beside this file builds them) under a residual
of ``hc_mult`` streams mixed by manifold-constrained hyper-connections
(arXiv:2512.24880), and a multi-token-prediction module as a second loss
(DeepSeek-V3 report, section 2.2). Written for XingChen-AGI's
Xing4.0-29B-A4B (https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B).

``get_symbol(config)`` builds, from the keys of the published
``config.json``, with ``n = hc_mult`` and a token's residual X [n, d]
carried as [B, T, n * d]:

    X = the embedding, copied into all n streams
    every sublayer F (attention; dense MLP or expert layer), with its own
    mixing parameters:
        y, coef, X = HyperPre(X)            y = sum_j H_pre[j] X[j]
        X = HyperPost(X, F(RMSNorm(y)), coef)
                                            X'[i] = H_post[i] z + sum_j M[i, j] X[j]
    h = HyperCollapse(X)                    sum_j sigmoid(.)[j] X[j]
    softmax = SoftmaxOutput(head(RMSNorm(h)), softmax_label)

and, for ``num_nextn_predict_layers`` 1, the prediction module:

    h' = [RMSNorm(h) ; RMSNorm(embed(softmax_label))] W_eh
    one more sparse block on h' copied into n streams, collapsed as above
    mtp_softmax = SoftmaxOutput(head(RMSNorm(.)), the label one token on,
                                grad_scale=mtp_loss_weight)

with the embedding and the head shared by both heads (one ``Variable``
each, its gradient the sum). The module's row t predicts
``softmax_label[t + 1]``; its hidden rows are moved one down before the
last norm and the head (a zero row first), so that row t of
``mtp_softmax_output`` is a distribution for ``softmax_label[t]`` like the
main head's, and any metric that names ``softmax_label`` reads either
output. Row 0 has no prediction behind it: its hidden state is zero, its
logits are zero, its distribution uniform, and the loss ignores it
(``use_ignore``, ``normalization='valid'``), which is the report's "the
last position is ignored" seen from the label's side. The symbol is
``Group([softmax, mtp_softmax])`` with the one input ``data`` and the one
label ``softmax_label``; the objective is ``L_main + mtp_loss_weight
L_mtp`` (0.3 unless the configuration says otherwise).

What is not built raises: ``num_nextn_predict_layers`` above 1, and what
``deepseek_v3.py`` refuses (grouped routing, other rotary scalings).
``dtype`` and ``remat`` are as in ``laguna.py`` beside this file: each
block is one mirrored stage that keeps its attention kernel's output and
log-sum-exp. The ops are ``mxnet_tpu/ops/transformer.py``; the plain
reference that the tests and the benchmark compare with is
``benchmark/reference/xing4_0.py``.
"""
import mxnet_tpu as mx

from deepseek_v3 import Blocks


def get_symbol(config, dtype='float32', remat=True, **kwargs):
    cfg = config
    depth = int(cfg.get('num_nextn_predict_layers', 0))
    if depth > 1:
        raise ValueError('xing4_0: num_nextn_predict_layers %d: one '
                         'prediction module is built' % depth)
    net = Blocks(cfg, dtype)
    n, d, V = int(cfg['hc_mult']), net.d, int(cfg['vocab_size'])
    mixing = {'n': n, 'eps': net.eps,
              'sinkhorn_iters': int(cfg['hc_sinkhorn_iters']),
              'sinkhorn_eps': float(cfg['hc_eps']),
              'clamp_min': float(cfg['mhc_h_res_clamp_min']),
              'clamp_max': float(cfg['mhc_h_res_clamp_max'])}

    def mixing_inputs(p):
        return {'weight': net.var(p + '_weight'),
                'bias': net.var(p + '_bias_weight'),
                'alpha': net.var(p + '_alpha_gamma')}

    def sublayer(x, p, f):
        """x after the sublayer f (normed input -> update), mixed by the
        parameters ``<p>_hc_*``."""
        pre = mx.sym.HyperPre(
            data=x, stats=mx.sym.Variable(p + '_hc_stats', dtype='float32',
                                          init=mx.init.Zero()),
            name=p + '_hc', **mixing_inputs(p + '_hc'), **mixing)
        z = f(net.norm(pre[0], p + '_norm'))
        return mx.sym.HyperPost(data=pre[2], update=z, coef=pre[1], n=n,
                                name=p + '_hc_post')

    def block(x, name, sparse):
        p = name + '_attn'
        x = sublayer(x, p, lambda a: net.attention(a, p))
        return sublayer(x, name + '_mlp',
                        lambda b: net.feed_forward(b, name, sparse))

    def stage(x, name, sparse):
        if not remat:
            return block(x, name, sparse)
        with mx.AttrScope(__force_mirroring__=name):
            return block(x, name, sparse)

    def streams(h):
        return mx.sym.tile(h, reps=(1, 1, n))

    def collapse(x, p):
        return mx.sym.HyperCollapse(data=x, n=n, eps=net.eps, name=p,
                                    **mixing_inputs(p))

    def embed(ids, name):
        return mx.sym.Embedding(data=ids, weight=net.var('embed_weight'),
                                input_dim=V, output_dim=d, name=name)

    data = mx.sym.Variable('data', dtype='float32')
    label = mx.sym.Variable('softmax_label', dtype='float32')
    x = streams(embed(data, 'embed'))
    for i in range(int(cfg['num_hidden_layers'])):
        x = stage(x, 'layer%d' % i, net.is_sparse(i))
    h = collapse(x, 'head_hc')
    main = net.loss(h, label, 'softmax', 'final_norm')
    if not depth:
        return main

    joined = mx.sym.Concat(net.norm(h, 'mtp_h_norm'),
                           net.norm(embed(label, 'mtp_embed'), 'mtp_e_norm'),
                           dim=2)
    x = stage(streams(net.linear(joined, 'mtp_eh', d)), 'mtp', True)
    g = collapse(x, 'mtp_head_hc')
    # one row down: row t then stands for softmax_label[t]
    first = mx.sym.slice_axis(g, axis=1, begin=0, end=1)
    g = mx.sym.Concat(first * 0.0, mx.sym.slice_axis(g, axis=1, begin=0,
                                                     end=-1), dim=1)
    first = mx.sym.slice_axis(label, axis=1, begin=0, end=1)
    seen = mx.sym.Concat(first * 0.0 - 1.0,
                         mx.sym.slice_axis(label, axis=1, begin=1, end=None),
                         dim=1)
    mtp = net.loss(g, seen, 'mtp_softmax', 'mtp_final_norm',
                   use_ignore=True, ignore_label=-1,
                   grad_scale=float(cfg.get('mtp_loss_weight', 0.3)))
    return mx.sym.Group([main, mtp])
