"""Shape/type inference over a Symbol graph.

Reference: src/executor/infer_graph_attr_pass.cc:302-338 (InferShape/
InferType fixpoint over per-op FInferShape) — the piece of the reference's
bind pipeline that must stay host-side even in the XLA world, because
simple_bind allocates parameter arrays before any tracing happens.

Design: forward topo walk with jax.eval_shape per node; unknown *parameter*
shapes are filled by per-op hooks keyed on the data input's shape + attrs
(the practically-used direction of the reference's bidirectional solver).
"""
import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError, np_dtype
from ..ops import registry as _reg

__all__ = ['infer_shapes', 'infer_types', 'param_shape_hook']

_PARAM_HOOKS = {}


def param_shape_hook(op_name):
    def deco(fn):
        _PARAM_HOOKS[op_name] = fn
        return fn
    return deco


@param_shape_hook('FullyConnected')
def _fc_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    flat = int(np.prod(data[1:])) if attrs.get('flatten', True) else data[-1]
    n = int(attrs['num_hidden'])
    out = {'weight': (n, flat)}
    if not attrs.get('no_bias', False):
        out['bias'] = (n,)
    return out


@param_shape_hook('Convolution')
def _conv_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    nf = int(attrs['num_filter'])
    g = int(attrs.get('num_group', 1))
    kernel = tuple(attrs['kernel'])
    out = {'weight': (nf, data[1] // g) + kernel}
    if not attrs.get('no_bias', False):
        out['bias'] = (nf,)
    return out


@param_shape_hook('_contrib_DeformableConvolution')
def _deform_conv_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    nf = int(attrs['num_filter'])
    g = int(attrs.get('num_group', 1))
    kernel = tuple(attrs['kernel'])
    out = {'weight': (nf, data[1] // g) + kernel}
    if not attrs.get('no_bias', False):
        out['bias'] = (nf,)
    return out


@param_shape_hook('Deconvolution')
def _deconv_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    nf = int(attrs['num_filter'])
    g = int(attrs.get('num_group', 1))
    kernel = tuple(attrs['kernel'])
    out = {'weight': (data[1], nf // g) + kernel}
    if not attrs.get('no_bias', True):
        out['bias'] = (nf,)
    return out


@param_shape_hook('BatchNorm')
def _bn_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    ax = int(attrs.get('axis', 1)) % len(data)
    c = data[ax]
    return {'gamma': (c,), 'beta': (c,), 'moving_mean': (c,), 'moving_var': (c,)}


@param_shape_hook('InstanceNorm')
def _in_params(attrs, in_shapes):
    data = in_shapes[0]
    return {'gamma': (data[1],), 'beta': (data[1],)} if data else {}


@param_shape_hook('LayerNorm')
def _ln_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    ax = int(attrs.get('axis', -1)) % len(data)
    return {'gamma': (data[ax],), 'beta': (data[ax],)}


@param_shape_hook('RMSNorm')
def _rms_params(attrs, in_shapes):
    data = in_shapes[0]
    return {'gamma': (data[-1],)} if data else {}


@param_shape_hook('GatedMLP')
def _gated_mlp_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None or not attrs.get('hidden'):
        return {}
    d, h = data[-1], int(attrs['hidden'])
    return {'w1_weight': (h, d), 'w3_weight': (h, d), 'w2_weight': (d, h)}


@param_shape_hook('GatedShortConv')
def _gated_short_conv_params(attrs, in_shapes):
    data = in_shapes[0]
    return {'weight': (data[-1] // 3, int(attrs.get('kernel', 3)))} \
        if data else {}


@param_shape_hook('ShortConv')
def _short_conv_params(attrs, in_shapes):
    data = in_shapes[0]
    return {'weight': (data[-1], int(attrs.get('kernel', 4)))} \
        if data else {}


@param_shape_hook('GatedDeltaRule')
def _gated_delta_rule_params(attrs, in_shapes):
    from ..ops.transformer import DELTA_STATS
    return {'stats': (len(DELTA_STATS),)}


@param_shape_hook('MoE')
def _moe_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None or not attrs.get('hidden'):
        return {}
    from ..ops.transformer import MOE_STATS
    d, h = data[-1], int(attrs['hidden'])
    sh = int(attrs.get('shared_hidden') or 0)   # 0: no shared expert
    held = int(attrs['experts_held'])
    return {'router_weight': (int(attrs['num_experts']), d),
            'experts_w1_weight': (held, d, h),
            'experts_w3_weight': (held, d, h),
            'experts_w2_weight': (held, h, d),
            'shared_w1_weight': (sh, d), 'shared_w3_weight': (sh, d),
            'shared_w2_weight': (d, sh), 'stats': (len(MOE_STATS),),
            'select_bias': (1, int(attrs['num_experts']))}


@param_shape_hook('HyperPre')
def _hyper_pre_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    from ..ops.transformer import HYPER_STATS
    n = int(attrs.get('n', 4))
    k = 2 * n + n * n
    return {'weight': (k, data[-1]), 'bias': (1, k), 'alpha': (3,),
            'stats': (len(HYPER_STATS),)}


@param_shape_hook('HyperCollapse')
def _hyper_collapse_params(attrs, in_shapes):
    data = in_shapes[0]
    if data is None:
        return {}
    n = int(attrs.get('n', 4))
    return {'weight': (n, data[-1]), 'bias': (1, n), 'alpha': (1,)}


@param_shape_hook('Embedding')
def _emb_params(attrs, in_shapes):
    return {'weight': (int(attrs['input_dim']), int(attrs['output_dim']))}


@param_shape_hook('LeakyReLU')
def _lrelu_params(attrs, in_shapes):
    if attrs.get('act_type') == 'prelu' and in_shapes[0]:
        return {'gamma': (in_shapes[0][1],)}
    return {}


@param_shape_hook('RNN')
def _rnn_params(attrs, in_shapes):
    from ..ops.rnn_ops import rnn_param_size
    data = in_shapes[0]
    if data is None:
        return {}
    H = int(attrs['state_size'])
    L = int(attrs.get('num_layers', 1))
    bi = bool(attrs.get('bidirectional', False))
    dirs = 2 if bi else 1
    mode = attrs.get('mode', 'lstm')
    n = rnn_param_size(L, H, data[2], bi, mode)
    out = {'parameters': (n,), 'state': (L * dirs, data[1], H)}
    if mode == 'lstm':
        out['state_cell'] = (L * dirs, data[1], H)
    return out


@param_shape_hook('SoftmaxOutput')
def _softmax_out_params(attrs, in_shapes):
    """Reference softmax_output-inl.h label inference from the data
    shape: (N,) by default; (N, d2, ...) with multi_output (class axis
    1 removed); data shape minus the last axis with preserve_shape."""
    data = in_shapes[0]
    if data is None:
        return {}
    if attrs.get('preserve_shape', False):
        return {'label': tuple(data[:-1])}
    if attrs.get('multi_output', False):
        return {'label': (data[0],) + tuple(data[2:])}
    return {'label': (data[0],)}


@param_shape_hook('SVMOutput')
def _svm_out_params(attrs, in_shapes):
    data = in_shapes[0]
    return {'label': (data[0],)} if data else {}


def _reg_out_params(attrs, in_shapes):
    """Regression outputs: label has the data's shape (reference
    regression_output-inl.h)."""
    data = in_shapes[0]
    return {'label': tuple(data)} if data else {}


for _name in ('LinearRegressionOutput', 'MAERegressionOutput',
              'LogisticRegressionOutput'):
    param_shape_hook(_name)(_reg_out_params)


def _node_arg_name(node, i):
    op = node.opdef()
    names = op.names_present(node.attrs)
    return names[i] if i < len(names) else 'arg%d' % i


def infer_shapes(symbol, known, partial=False, known_types=None):
    """Returns (arg_shapes, out_shapes, aux_shapes) in canonical orders."""
    known_types = known_types or {}
    shapes = {}   # id(node) -> tuple per output
    var_shape = {}

    for n in symbol._topo():
        if n.is_variable():
            s = known.get(n.name)
            if s is None and '__shape__' in n.attr_dict:
                import ast
                s = tuple(ast.literal_eval(n.attr_dict['__shape__']))
            if s is not None and any(d == 0 for d in s):
                s = None  # 0-dims mean "unknown" (MXNet convention)
            var_shape[n.name] = tuple(s) if s is not None else None
            shapes[id(n)] = [var_shape[n.name]]
            continue
        op = n.opdef()
        in_shapes = []
        for (p, idx) in n.inputs:
            sh = shapes.get(id(p))
            in_shapes.append(sh[idx] if sh is not None and sh[idx] is not None else None)
        # fill unknown parameter-variable shapes via hook
        hook = _PARAM_HOOKS.get(n.op)
        if hook is not None:
            fills = hook(n.attrs, in_shapes)
            for i, (p, idx) in enumerate(n.inputs):
                if in_shapes[i] is None and p.is_variable():
                    want = fills.get(_node_arg_name(n, i))
                    if want is not None:
                        var_shape[p.name] = tuple(int(x) for x in want)
                        shapes[id(p)] = [var_shape[p.name]]
                        in_shapes[i] = var_shape[p.name]
        if any(s is None for s in in_shapes):
            if partial:
                shapes[id(n)] = [None] * op.n_outputs(n.attrs)
                continue
            missing = [_node_arg_name(n, i) for i, s in enumerate(in_shapes) if s is None]
            raise MXNetError('cannot infer shape for inputs %s of node %s(%s)'
                             % (missing, n.name, n.op))
        out_shapes = _eval_node_shape(n, in_shapes, known_types)
        shapes[id(n)] = out_shapes

    args = symbol.list_arguments()
    auxs = symbol.list_auxiliary_states()
    arg_shapes = [var_shape.get(a) for a in args]
    aux_shapes = [var_shape.get(a) for a in auxs]
    out_shapes = []
    for node, idx in symbol._outputs:
        s = shapes.get(id(node))
        out_shapes.append(s[idx] if s else None)
    return arg_shapes, out_shapes, aux_shapes


def _eval_node_shape(n, in_shapes, known_types):
    op = n.opdef()
    attrs = dict(n.attrs)
    if op.train_aware:
        attrs['__is_train__'] = False
    specs = [jax.ShapeDtypeStruct(s, np_dtype(known_types.get(None, 'float32')))
             for s in in_shapes]
    if op.needs_rng:
        specs.append(jax.ShapeDtypeStruct((2,), np.uint32))

    if op.host:
        # host ops cannot be traced; their shape contract comes from
        # shape_fn (legacy infer_shape callbacks, codec geometry)
        if op.shape_fn is None:
            raise MXNetError(
                'host op %s(%s) has a data-dependent output shape; it can '
                'only be used imperatively' % (n.name, n.op))
        out_shapes, _ = op.shape_fn(attrs, [tuple(s) for s in in_shapes])
        return [tuple(s) for s in out_shapes]

    def f(*arrays):
        return op.fn(attrs, *arrays)
    try:
        out = jax.eval_shape(f, *specs)
    except Exception as e:
        raise MXNetError('shape inference failed at %s(%s) with inputs %s: %s'
                         % (n.name, n.op, in_shapes, e))
    if not isinstance(out, (tuple, list)):
        out = (out,)
    return [tuple(o.shape) for o in out]


def _is_floating(t):
    """Floating check covering bfloat16 (outside numpy's hierarchy;
    ``t`` may be a np.dtype, a numpy scalar type, or the jnp.bfloat16
    class)."""
    dt = np.dtype(t)
    return dt.name in ('bfloat16', 'float16') or \
        np.issubdtype(dt, np.floating)


def infer_types(symbol, known):
    dtypes = {}
    var_dtype = {}
    for n in symbol._topo():
        if n.is_variable():
            t = known.get(n.name)
            if t is None and '__dtype__' in n.attr_dict:
                t = n.attr_dict['__dtype__']
            # None = not yet known; resolved from the first consumer
            # below (the practical direction of the reference's
            # bidirectional InferType fixpoint — parameters of a bf16
            # node become bf16)
            var_dtype[n.name] = np_dtype(t) if t is not None else None
            dtypes[id(n)] = [var_dtype[n.name]]
            continue
        in_dtypes = [dtypes[id(p)][i] for (p, i) in n.inputs]
        # seed from the first FLOATING known input: integer inputs
        # (Embedding/take indices) must not type float parameters
        seed = next((t for t in in_dtypes if t is not None
                     and _is_floating(t)), np.dtype('float32'))
        for (p, i), t in zip(n.inputs, in_dtypes):
            if t is None and p.is_variable():
                var_dtype[p.name] = seed
                dtypes[id(p)] = [seed]
        in_dtypes = [dtypes[id(p)][i] for (p, i) in n.inputs]
        # forward propagate: result dtype = first input (simplified)
        out_t = in_dtypes[0] if in_dtypes else np.dtype('float32')
        if n.op == 'Cast':
            out_t = np_dtype(n.attrs['dtype'])
        op = n.opdef()
        dtypes[id(n)] = [out_t] * op.n_outputs(n.attrs)
    args = symbol.list_arguments()
    auxs = symbol.list_auxiliary_states()
    f32 = np.dtype('float32')
    outs = [dtypes[id(node)][idx] or f32 for node, idx in symbol._outputs]
    return ([var_dtype.get(a) or f32 for a in args], outs,
            [var_dtype.get(a) or f32 for a in auxs])
