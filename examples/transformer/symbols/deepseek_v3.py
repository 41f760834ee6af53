"""Decoders of the ``deepseek_v3`` family as a Symbol for ``Module.fit``:
latent attention (MLA), a sigmoid router with a selection bias, shared
experts. Written for kakaocorp's kanana-2-30b-a3b-instruct-2601
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601).

``get_symbol(config)`` builds the network from the keys of the model's
published ``config.json``: an embedding, ``num_hidden_layers`` blocks, a
last RMSNorm and an untied head, trained on the mean cross-entropy of the
next token. A block is

    a = RMSNorm(h);  q = a Wq;  [c_kv | k_rope] = a Wa
    [k_nope | v] = RMSNorm(c_kv) Wb        (per head)
    h = h + LatentAttention(q_nope, rope(q_rope), k_nope, rope(k_rope), v) Wo
    b = RMSNorm(h);  h = h + MLP(b)        (layers before first_k_dense_replace)
                     h = h + MoE(b)        (the others)

with ``num_attention_heads`` heads whose keys are ``qk_nope_head_dim +
qk_rope_head_dim`` wide and whose values ``v_head_dim``, the keys and values
expanded from a latent of ``kv_lora_rank`` (training computes this form;
the absorbed one is a decode path), one rotary key head shared by all
heads, interleaved rotary pairs (``rope_interleave``), and
``n_routed_experts`` routed experts scored by a sigmoid, chosen by score
plus selection bias, of which this program holds ``experts_held`` from
``expert_offset`` on (all of them by default) beside ``n_shared_experts``
shared ones, which are one gated MLP of that many times the width. With
``q_lora_rank`` set the queries come from a latent of that rank (``q =
RMSNorm(a Wqa) Wqb``); ``rope_scaling`` of type ``yarn`` gives the rotary
heads YaRN's frequencies and, through ``mscale_all_dim``, the scores a
factor beside ``1 / sqrt(Dn + Dr)``. Not built, here or beside: grouped
routing (``n_group`` other than 1), other rotary scalings; they raise.
Residual streams and a second, multi-token-prediction head are built by
``xing4_0.py`` beside this file, from this file's ``Blocks``. The ops are
``mxnet_tpu/ops/transformer.py``; the plain reference that the tests and
the benchmark compare with is ``benchmark/reference/deepseek_v3.py``.

The selection bias is the argument ``layerN_moe_select_bias_weight`` of
shape ``(1, n_routed_experts)``; it takes no gradient (``MoE`` stops it),
so an optimizer without weight decay leaves it as given.

``data``, ``softmax_label``, the output, ``dtype`` and ``remat`` are as in
``laguna.py`` beside this file: each block is one mirrored stage that
keeps the attention kernel's output and log-sum-exp.
"""
import math

import mxnet_tpu as mx


def _check(cfg):
    """Raises for what this file does not build. Built here: ``q_lora_rank``
    (a low-rank query path) and ``rope_scaling`` of type ``yarn``. Not
    built anywhere: grouped routing, other rotary scalings, a softmax
    router. A second (multi-token-prediction) head and residual streams
    are ``xing4_0.py``'s, beside this file, over these same blocks."""
    if int(cfg.get('n_group', 1)) != 1 or int(cfg.get('topk_group', 1)) != 1:
        raise ValueError('deepseek_v3: grouped routing (n_group %r, '
                         'topk_group %r) is not built'
                         % (cfg.get('n_group'), cfg.get('topk_group')))
    scaling = cfg.get('rope_scaling')
    if scaling is not None and scaling.get(
            'type', scaling.get('rope_type')) != 'yarn':
        raise ValueError('deepseek_v3: rope_scaling %r is not built'
                         % (scaling,))
    if cfg.get('scoring_func', 'sigmoid') != 'sigmoid':
        raise ValueError('deepseek_v3: scoring_func %r'
                         % (cfg['scoring_func'],))


def yarn_mscale(factor, mscale):
    """The family's ``yarn_get_mscale``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary(cfg):
    """(attributes of ``RotaryEmbedding``, the scores' scale or 0 for the
    op's own ``1 / sqrt(Dn + Dr)``) from ``rope_theta`` and
    ``rope_scaling``. YaRN as this family writes it: cos and sin are
    multiplied by ``mscale / mscale_all_dim`` (each through
    :func:`yarn_mscale`) and, where ``mscale_all_dim`` is set, the scores
    by its square."""
    rope = {'base': float(cfg['rope_theta']),
            'interleaved': bool(cfg.get('rope_interleave', False))}
    scaling = cfg.get('rope_scaling')
    if scaling is None:
        return rope, 0.0
    factor = float(scaling['factor'])
    all_dim = float(scaling.get('mscale_all_dim', 0) or 0)
    rope.update(
        scaling='yarn', factor=factor,
        original_max_position=int(
            scaling['original_max_position_embeddings']),
        beta_fast=float(scaling.get('beta_fast', 32)),
        beta_slow=float(scaling.get('beta_slow', 1)),
        attention_factor=yarn_mscale(factor, float(scaling.get('mscale', 1)))
        / yarn_mscale(factor, all_dim))
    width = int(cfg['qk_nope_head_dim']) + int(cfg['qk_rope_head_dim'])
    return rope, (width ** -0.5 * yarn_mscale(factor, all_dim) ** 2
                  if all_dim else 0.0)


class Blocks:
    """The family's sublayers as symbols, from the published keys: what
    ``get_symbol`` here and in ``xing4_0.py`` compose."""

    def __init__(self, cfg, dtype):
        _check(cfg)
        self.cfg, self.dtype = cfg, dtype
        self.d = int(cfg['hidden_size'])
        self.eps = float(cfg.get('rms_norm_eps', 1e-6))
        self.rope, self.scale = rotary(cfg)
        self._vars = {}

    def var(self, name):
        """The parameter `name`: one ``Variable`` however often it is
        read (a head that two losses share takes the sum of both
        gradients)."""
        if name not in self._vars:
            self._vars[name] = mx.sym.Variable(name, dtype=self.dtype)
        return self._vars[name]

    def linear(self, x, name, out):
        return mx.sym.FullyConnected(
            data=x, weight=self.var(name + '_weight'), num_hidden=out,
            no_bias=True, flatten=False, name=name)

    def norm(self, x, name):
        return mx.sym.RMSNorm(data=x, gamma=self.var(name + '_gamma'),
                              eps=self.eps, name=name)

    def is_sparse(self, i):
        cfg = self.cfg
        return i >= int(cfg.get('first_k_dense_replace', 0)) \
            and i % int(cfg.get('moe_layer_freq', 1)) == 0

    def attention(self, a, p):
        """The attention sublayer on the normed input a; parameters
        ``<p>_*``."""
        cfg = self.cfg
        H = int(cfg['num_attention_heads'])
        Dn, Dr = int(cfg['qk_nope_head_dim']), int(cfg['qk_rope_head_dim'])
        Dv, rank = int(cfg['v_head_dim']), int(cfg['kv_lora_rank'])
        linear, norm = self.linear, self.norm

        def columns(x, begin, end):
            return mx.sym.slice_axis(x, axis=-1, begin=begin, end=end)

        def per_head(x, width, begin, end):
            """Columns [begin, end) of every head of x [B, T, H * width]."""
            heads = mx.sym.Reshape(x, shape=(0, 0, H, width))
            return mx.sym.Reshape(columns(heads, begin, end),
                                  shape=(0, 0, -1))

        if cfg.get('q_lora_rank') is None:
            q = linear(a, p + '_q', H * (Dn + Dr))
        else:       # the low-rank query path: a latent, normed, expanded
            q = linear(norm(linear(a, p + '_q_a', int(cfg['q_lora_rank'])),
                            p + '_q_a_norm'), p + '_q_b', H * (Dn + Dr))
        c = linear(a, p + '_kv_a', rank + Dr)
        kv = linear(norm(columns(c, 0, rank), p + '_kv_norm'), p + '_kv_b',
                    H * (Dn + Dv))
        scale = {'scale': self.scale} if self.scale else {}
        o = mx.sym.LatentAttention(
            q_nope=per_head(q, Dn + Dr, 0, Dn),
            q_rope=mx.sym.RotaryEmbedding(
                per_head(q, Dn + Dr, Dn, Dn + Dr), num_heads=H,
                name=p + '_q_rope', **self.rope),
            k_nope=per_head(kv, Dn + Dv, 0, Dn),
            k_rope=mx.sym.RotaryEmbedding(
                columns(c, rank, rank + Dr), num_heads=1,
                name=p + '_k_rope', **self.rope),
            value=per_head(kv, Dn + Dv, Dn, Dn + Dv),
            num_heads=H, name=p, **scale)
        return linear(o, p + '_o', self.d)

    def feed_forward(self, b, name, sparse):
        """The expert layer (parameters ``<name>_moe_*``) or the dense MLP
        (``<name>_mlp_*``) on the normed input b."""
        cfg, var = self.cfg, self.var
        if not sparse:
            p = name + '_mlp'
            return mx.sym.GatedMLP(
                data=b, w1_weight=var(p + '_w1_weight'),
                w3_weight=var(p + '_w3_weight'),
                w2_weight=var(p + '_w2_weight'),
                hidden=int(cfg['intermediate_size']), name=p)
        experts = int(cfg['n_routed_experts'])
        p = name + '_moe'
        return mx.sym.MoE(
            data=b, router_weight=var(p + '_router_weight'),
            experts_w1_weight=var(p + '_experts_w1_weight'),
            experts_w3_weight=var(p + '_experts_w3_weight'),
            experts_w2_weight=var(p + '_experts_w2_weight'),
            shared_w1_weight=var(p + '_shared_w1_weight'),
            shared_w3_weight=var(p + '_shared_w3_weight'),
            shared_w2_weight=var(p + '_shared_w2_weight'),
            stats=mx.sym.Variable(p + '_stats', dtype='float32',
                                  init=mx.init.Zero()),
            select_bias=var(p + '_select_bias_weight'),
            scoring='sigmoid', num_experts=experts,
            experts_held=int(cfg.get('experts_held', experts)),
            expert_offset=int(cfg.get('expert_offset', 0)),
            num_experts_per_tok=int(cfg['num_experts_per_tok']),
            norm_topk_prob=bool(cfg.get('norm_topk_prob', True)),
            routed_scaling=float(cfg.get('routed_scaling_factor', 1.0)),
            hidden=int(cfg['moe_intermediate_size']),
            shared_hidden=int(cfg['moe_intermediate_size'])
            * int(cfg['n_shared_experts']),
            name=p)

    def loss(self, h, label, name, norm_name, **softmax):
        """The head on the hidden states h: a last RMSNorm, the untied
        head ``head_weight`` and the mean cross-entropy as
        ``SoftmaxOutput``."""
        V = int(self.cfg['vocab_size'])
        logits = mx.sym.FullyConnected(
            data=self.norm(h, norm_name), weight=self.var('head_weight'),
            num_hidden=V, no_bias=True, flatten=False,
            name='head' if name == 'softmax' else name + '_head')
        if self.dtype == 'float16':
            logits = mx.sym.Cast(data=logits, dtype='float32')
        return mx.sym.SoftmaxOutput(
            data=mx.sym.Reshape(logits, shape=(-1, V)),
            label=mx.sym.Reshape(label, shape=(-1,)), normalization='valid',
            name=name, **softmax)


def get_symbol(config, dtype='float32', remat=True, **kwargs):
    cfg = config
    net = Blocks(cfg, dtype)
    layers = int(cfg['num_hidden_layers'])

    def block(h, i):
        name = 'layer%d' % i
        p = name + '_attn'
        h = h + net.attention(net.norm(h, p + '_norm'), p)
        b = net.norm(h, name + '_mlp_norm')
        return h + net.feed_forward(b, name, net.is_sparse(i))

    data = mx.sym.Variable('data', dtype='float32')
    label = mx.sym.Variable('softmax_label', dtype='float32')
    h = mx.sym.Embedding(data=data, weight=net.var('embed_weight'),
                         input_dim=int(cfg['vocab_size']), output_dim=net.d,
                         name='embed')
    for i in range(layers):
        if remat:
            with mx.AttrScope(__force_mirroring__='layer%d' % i):
                h = block(h, i)
        else:
            h = block(h, i)
    return net.loss(h, label, 'softmax', 'final_norm')
