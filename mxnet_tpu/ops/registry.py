"""Single operator registry — the NNVM Op registry re-imagined for XLA.

Reference: nnvm Op registry + include/mxnet/op_attr_types.h (FCompute,
FResourceRequest, mutable inputs) and src/nnvm/legacy_op_util.cc (which
bridged two registries — here there is deliberately ONE registry, per
SURVEY.md §2.1 N7's note).

Each op declares a pure JAX implementation ``fn(attrs, *arrays)``; everything
else (shape/type inference, gradient, kernel fusion, memory planning) is
derived by tracing/compiling that function with XLA — the whole
attach-op/plan-memory pass pipeline of src/executor collapses into jax.jit.

Conventions:
- ``fn`` returns a single array or a tuple of arrays.
- ops mutating inputs in the reference (BatchNorm moving stats — see
  include/mxnet/op_attr_types.h FMutateInputs) declare ``mutate_inputs``:
  a dict {input_index: extra_output_index}; the invoke layer writes those
  extra outputs back into the input NDArrays, preserving the reference's
  aux-state semantics under a functional compiler.
- ``train_aware`` ops receive ``__is_train__`` in attrs.
- ``needs_rng`` ops receive a uint32 PRNG key as their LAST array argument.
"""
import functools
import os
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..base import MXNetError, normalize_attrs, attr_key

__all__ = ['OpDef', 'register', 'get', 'list_ops', 'jitted']

_OPS = {}


class OpDef:
    def __init__(self, name, fn, num_outputs=1, input_names=None,
                 param_defaults=None, differentiable=True, variadic=False,
                 mutate_inputs=None, needs_rng=False, num_visible_outputs=None,
                 train_aware=False, aux_inputs=(), key_var_num_args=None,
                 host=False, shape_fn=None, doc=None, optional_inputs=None):
        self.name = name
        self.fn = fn
        self.num_outputs = num_outputs  # int or callable(attrs)->int
        # an explicit [] means a nullary op (_zeros, _arange, samplers);
        # only None falls back to the single-'data' convention
        self.input_names = (['data'] if input_names is None
                            else list(input_names))
        self.param_defaults = param_defaults or {}
        self.differentiable = differentiable
        self.variadic = variadic  # takes *args (Concat/add_n style)
        self.mutate_inputs = mutate_inputs or {}
        self.needs_rng = needs_rng
        self.num_visible_outputs = num_visible_outputs  # int or callable
        self.train_aware = train_aware
        self.aux_inputs = tuple(aux_inputs)  # names of inputs that are aux states
        self.key_var_num_args = key_var_num_args  # attr naming the input count
        # host ops run python/numpy on concrete arrays (image codecs,
        # legacy callback bridges). Inside traced programs they ride
        # jax.pure_callback, which needs shape_fn(attrs, in_shapes) ->
        # (out_shapes, out_dtypes); without one the op is imperative-only.
        self.host = host
        self.shape_fn = shape_fn
        # {input_name: gate_attr}: the input exists only when the gate
        # attr is truthy (CTCLoss lengths, Sequence* sequence_length) —
        # keeps symbol compose from fabricating variables for them; a
        # callable gate is asked with the attrs (MoE's select_bias)
        self.optional_inputs = dict(optional_inputs or {})
        self.doc = doc or (fn.__doc__ or '')

    def n_outputs(self, attrs):
        n = self.num_outputs
        return n(attrs) if callable(n) else n

    def n_visible_outputs(self, attrs):
        n = self.num_visible_outputs
        if n is None:
            return self.n_outputs(attrs)
        return n(attrs) if callable(n) else n

    def arg_names(self, attrs=None, num_args=None):
        """Input names; variadic ops expand arg0..argN-1. Optional
        inputs are dropped unless their gate attr is truthy."""
        if self.variadic:
            n = num_args if num_args is not None else 0
            return ['arg%d' % i for i in range(n)]
        names = list(self.input_names)
        if self.optional_inputs:
            attrs = attrs or {}
            def _on(gate):
                if callable(gate):
                    return gate(attrs)
                v = attrs.get(gate, self.param_defaults.get(gate, False))
                return v not in (False, 'False', '0', 0, None, 'false')
            names = [n for n in names
                     if n not in self.optional_inputs
                     or _on(self.optional_inputs[n])]
        return names

    def names_present(self, attrs=None):
        """The declared name of each input of a node with `attrs`, by
        place: ``input_names`` less the optional inputs that are off."""
        return self.arg_names(attrs) if self.optional_inputs \
            else self.input_names

    def mutated(self, attrs=None):
        """``mutate_inputs`` by an input's place among the inputs present
        (its keys count every declared input: an optional input that is
        absent moves the ones after it up)."""
        if not (self.optional_inputs and self.mutate_inputs):
            return self.mutate_inputs
        present = self.names_present(attrs)
        return {present.index(self.input_names[i]): out
                for i, out in self.mutate_inputs.items()
                if self.input_names[i] in present}


def register(name, **kwargs):
    """Decorator registering ``fn(attrs, *arrays)`` as operator ``name``."""
    def deco(fn):
        op = OpDef(name, fn, **kwargs)
        _OPS[name] = op
        return fn
    return deco


def register_alias(alias, name):
    _OPS[alias] = _OPS[name]


def get(name):
    op = _OPS.get(name)
    if op is None:
        raise KeyError('operator %r is not registered' % (name,))
    return op


def exists(name):
    return name in _OPS


def list_ops():
    return sorted(_OPS)


def op_alias_groups():
    """Registration names grouped by shared OpDef: [[name, alias, ...]].
    The single source of alias resolution for the coverage gates
    (tests/conftest.py, test_op_sweep.py) — invoking any name in a
    group covers the whole group."""
    groups = {}
    for n in list_ops():
        groups.setdefault(id(_OPS[n]), []).append(n)
    return list(groups.values())


# -- execution-based coverage bookkeeping (tests/conftest.py gate) ----------
# Recording is keyed off the env at import so the per-invoke cost is a
# single branch when off. Canonical op names land in _INVOKED at every
# execution chokepoint (eager jit closures, apply_op, host bridges, the
# executor's traced/staged node loops); atexit appends them to
# MXTPU_OP_COVERAGE_FILE so subprocess test cases (examples, compat
# scripts) count toward the suite-wide union.
_INVOKED = set()
_COVERAGE_FILE = os.environ.get('MXTPU_OP_COVERAGE_FILE', '')
_COVERING = bool(_COVERAGE_FILE) or \
    os.environ.get('MXTPU_OP_COVERAGE', '') not in ('', '0')


def record(op):
    if _COVERING:
        _INVOKED.add(op.name)


def invoked_names():
    return frozenset(_INVOKED)


def _flush_invoked():
    if _COVERAGE_FILE and _INVOKED:
        try:
            with open(_COVERAGE_FILE, 'a') as f:
                f.write('\n'.join(sorted(_INVOKED)) + '\n')
        except OSError:
            pass


if _COVERING:
    import atexit
    atexit.register(_flush_invoked)


# -- values dear to recompute -------------------------------------------------
# A mirrored stage recomputes in the backward pass everything but what an op
# has named here: a value that its backward pass reads anyway and that costs
# more to make again than to hold. Five rules, from shapes and structure alone:
#   - an attention op names its kernel's output and log-sum-exp, and the
#     operands that the backward kernel reads: query, key and value, the
#     projections, per-head splits and rotary turns behind them. (Latent
#     attention leaves out the keys and values it expands from the latent:
#     the expansion is cheap and eight times the latent's size);
#   - ``FullyConnected`` names its output where it contracts (no more
#     output features than input features): the value is no larger than
#     the one it was made from, behind a product as deep as it is wide;
#   - ``MoE`` names its routing and its plan: a few small vectors behind
#     a top-k, a gather and a sort's worth of scans and scatters;
#   - ``GatedDeltaRule`` names what is sequential in it: the chain of
#     chunks' output and states, and each chunk's inverse (4 C^2 bytes
#     behind a solve; W, U and P are five products away from it);
#   - a gated MLP names its two hidden products, float32 as made: 2 d_in
#     operations an element for 8 bytes written and read, T / 3.75 d_in of
#     its weights, masters and momentum together; silu(g) * u is made again.
# `GatedShortConv` and the projection that feeds it are made again too.

_DEAR = set()                   # every name `dear` was given
_MIRROR = threading.local()     # .kept: the list of the stage being traced


def dear(x, name):
    """Name ``x`` as dear to recompute: the identity, except that a
    mirrored stage keeps the value for its backward pass. The policy
    judges a value where it is made, and a backward rule reads the values
    its forward rule saw: so name a ``jax.custom_vjp``'s operands before
    the call and, of what its forward rule makes, the residuals themselves
    inside it (a value left unnamed there brings back the whole computation
    behind it); a primitive whose own rule reads its raw result
    (``top_k``'s indices) needs a rule that reads the named one. A name may
    be shared by call sites. Naming a named value again keeps, and counts,
    one array."""
    _DEAR.add(name)
    kept = getattr(_MIRROR, 'kept', None)
    named = checkpoint_name(x, name)
    if kept is not None:
        # named before: one array, under its last name
        at = next((i for i, k in enumerate(kept) if k is x), len(kept))
        kept[at:at + 1] = [named]
    return named


def keeps_dear(prim, *avals, **params):
    """The ``jax.checkpoint`` policy of a mirrored stage: keep what
    :func:`dear` named, recompute the rest. It reads the names when it is
    asked, which is after the stage has been traced; a stage in which no
    op named anything keeps nothing."""
    return jax.checkpoint_policies.save_only_these_names(*_DEAR)(
        prim, *avals, **params)


def mirrored(f, kept):
    """``f`` as a mirrored stage: its backward pass recomputes ``f`` from
    its inputs, except the values an op inside named as :func:`dear`
    (the rules are above), which are kept. With no value named that is a
    bare ``jax.checkpoint``. Called under ``jax.vjp``, the result appends
    each value it names to ``kept``."""
    stage = jax.checkpoint(f, policy=keeps_dear)

    def g(*args):
        outer = getattr(_MIRROR, 'kept', None)
        _MIRROR.kept = kept
        try:
            return stage(*args)
        finally:
            _MIRROR.kept = outer

    return g


@functools.lru_cache(maxsize=None)
def _jitted_impl(name, akey):
    op = _OPS[name]
    record(op)
    attrs = dict(akey)

    def f(*arrays):
        return op.fn(attrs, *arrays)
    f.__name__ = name
    return jax.jit(f)


def lazy_op_module(module_globals, make_fn, underscore_only=False):
    """Build (__getattr__, __dir__) for a generated-op module path
    (nd/sym ``op`` and ``_internal`` — reference ndarray/op.py etc.).
    Resolved functions are cached into the module's globals."""
    def __getattr__(name):
        if exists(name):
            fn = make_fn(name)
            module_globals[name] = fn
            return fn
        raise AttributeError('operator %r is not registered' % (name,))

    def __dir__():
        ops = list_ops()
        return [n for n in ops if n.startswith('_')] \
            if underscore_only else ops
    return __getattr__, __dir__


def jitted(name, attrs):
    """Cached jit-compiled closure for (op, attrs). jax.jit adds the
    shape/dtype-keyed cache on top — together these are the CachedOp
    (src/c_api/c_api_ndarray.cc:628) analog for the eager path."""
    return _jitted_impl(name, attr_key(normalize_attrs(attrs)))


def apply_op(name, attrs, *arrays):
    """Uncached direct application (used inside symbol executors where the
    surrounding graph is already being traced under one jit)."""
    op = _OPS[name]
    record(op)
    return op.fn(attrs, *arrays)


def host_bridge(op, attrs):
    """Traceable wrapper for a host op: jax.pure_callback (so the python
    runs host-side at execution time, the reference's ExecType::kLocal)
    plus a custom_vjp that calls the op's registered python `backward`
    when one exists (legacy PythonOp/NDArrayOp protocol) and returns
    zero cotangents otherwise (codecs are non-differentiable).

    Requires op.shape_fn; host ops without one (data-dependent output
    shapes, e.g. _cvimdecode) cannot enter traced programs."""
    record(op)
    import numpy as np
    if op.shape_fn is None:
        raise MXNetError(
            'host op %r has a data-dependent output shape and can only '
            'be used imperatively (nd.*), not inside a traced graph'
            % op.name)

    def specs_for(arrays):
        in_shapes = [tuple(a.shape) for a in arrays]
        out_shapes, out_dtypes = op.shape_fn(attrs, in_shapes)
        # a None dtype means "same as input 0"
        fallback = arrays[0].dtype if arrays else np.float32
        specs = tuple(jax.ShapeDtypeStruct(tuple(s),
                                           np.dtype(fallback if d is None else d))
                      for s, d in zip(out_shapes, out_dtypes))
        # single-output ops return a bare array (the op-fn convention)
        return specs[0] if len(specs) == 1 else specs

    def run_host(*arrays):
        outs = op.fn(attrs, *arrays)
        if isinstance(outs, (tuple, list)):
            return tuple(np.asarray(o) for o in outs)
        return np.asarray(outs)

    @jax.custom_vjp
    def call(*arrays):
        return jax.pure_callback(run_host, specs_for(arrays), *arrays)

    def fwd(*arrays):
        outs = jax.pure_callback(run_host, specs_for(arrays), *arrays)
        return outs, (arrays, outs)

    def bwd(res, gouts):
        arrays, outs = res
        backward = getattr(op, 'legacy_backward', None)
        if backward is None:
            return tuple(jnp.zeros(a.shape, a.dtype) for a in arrays)
        in_specs = tuple(jax.ShapeDtypeStruct(tuple(a.shape), np.dtype(a.dtype))
                         for a in arrays)
        gouts_t = gouts if isinstance(gouts, (tuple, list)) else (gouts,)
        outs_t = outs if isinstance(outs, (tuple, list)) else (outs,)

        def run_bwd(gouts_, ins_, outs_):
            return backward(attrs, gouts_, ins_, outs_)
        return jax.pure_callback(run_bwd, in_specs, gouts_t, arrays, outs_t)

    call.defvjp(fwd, bwd)
    return call
