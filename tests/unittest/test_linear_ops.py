"""The ops that a delta-rule linear-attention hybrid needs
(``examples/transformer/symbols/olmo_hybrid.py``), on the CPU in float32,
against the plain reference ``benchmark/reference/olmo_hybrid.py``, on both
dispatch paths (the recurrence under ``lax.scan`` and the chunked form with
its Pallas kernels, interpreted):

- ``GatedDeltaRule``: the output and all five gradients against the
  reference's recurrence, at a length that is a multiple of the chunk and
  at ones that are not, at one chunk and at several, with decays near 1 and
  near 0.5, with beta near 2, with one and with two heads a grid step;
- two derivations of the same thing: the kernels' state at the second
  chunk's start is the reference's state after the first chunk's last row,
  handed on by hand;
- the kernels of a chunk's state-free part (the solve, the chain's six
  operands, the five cotangents) against the same algebra in plain jnp
  under autodiff, and the inverse made inside the kernel against a solve;
- ``ShortConv`` against the reference's shifted sums, on both paths (the
  kernels are ``GatedShortConv``'s with their gates off);
- what a mirrored linear-attention block keeps (the chain of chunks' output
  and states, and the chunks' inverses) and that kept or made again they
  give the same gradients.
"""
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.transformer import DELTA_STATS, delta_stat_names

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(rel, name):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, *rel.split('/')))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load('benchmark/reference/olmo_hybrid.py', 'olmo_hybrid_reference')
builder = _load('examples/transformer/symbols/olmo_hybrid.py',
                'olmo_hybrid_symbol')
cases = _load('tests/unittest/test_transformer_ops.py',
              'transformer_ops_cases')
path, PATHS = cases.path, cases.PATHS
_rand, _close, _both, op = cases._rand, cases._close, cases._both, cases.op
_training_step = cases._training_step

CFG = dict(
    model_type='olmo_hybrid', vocab_size=96, hidden_size=64,
    intermediate_size=160, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=4, hidden_act='silu', rms_norm_eps=1e-6,
    tie_word_embeddings=False, attention_bias=False,
    layer_types=['linear_attention'] * 3 + ['full_attention'],
    linear_num_key_heads=4, linear_num_value_heads=4, linear_key_head_dim=12,
    linear_value_head_dim=24, linear_conv_kernel_dim=4,
    linear_allow_neg_eigval=True, rope_parameters={'rope_theta': None},
    linear_A_log_offset=0.0, linear_dt_bias_offset=-2.0)
T, dk, dv = 32, 12, 24
C = pk.DELTA_CHUNK


# -- the gated delta rule --------------------------------------------------------------------

@pytest.fixture
def small_kernel_bodies(monkeypatch):
    """The interpreter compiles a kernel's body op by op, and the chunk
    kernels' bodies are unrolled over heads and over the substitution's
    steps: one head a grid step and blocks of 16 rows for the tests that
    are not about those bodies."""
    monkeypatch.setattr(pk, '_DELTA_CHUNK_HEADS', 1)
    monkeypatch.setattr(pk, '_DELTA_SOLVE_ROWS', 16)


def _operands(B, length, H, decay, beta_at, seed=0):
    """q, k, v [B, T, H * D], g and beta [B, T, H]: the decay a row about
    `decay`, beta about `beta_at`."""
    rng = np.random.RandomState(seed)
    q, k = _rand(seed, B, length, H * dk), _rand(seed + 1, B, length, H * dk)
    v = _rand(seed + 2, B, length, H * dv)
    g = jnp.asarray(np.log(decay) * rng.uniform(0.5, 1.5, (B, length, H)),
                    jnp.float32)
    beta = jnp.asarray(np.clip(beta_at + 0.1 * rng.randn(B, length, H),
                               0.0, 2.0), jnp.float32)
    return q, k, v, g, beta


def _rule(H):
    fn = op('GatedDeltaRule', num_heads=H)
    stats = jnp.zeros((len(DELTA_STATS),), jnp.float32)
    return lambda *a: fn(*a, stats)[0]


def _reference_rule(H, keep_states=False):
    def one(q, k, v, g, beta):
        L = q.shape[0]
        return ref.delta_rule(
            ref.unit(q.reshape(L, H, dk), dk ** -0.5),
            ref.unit(k.reshape(L, H, dk)), v.reshape(L, H, dv), g, beta,
            keep_states=keep_states)

    if keep_states:
        return one
    return lambda *a: jnp.stack([one(*(x[b] for x in a)).reshape(
        a[0].shape[1], H * dv) for b in range(a[0].shape[0])])


RULE_CASES = {
    'one': (C, 2, 0.99, 1.0),                   # one chunk
    'two_beta2': (2 * C, 4, 0.95, 1.9),         # two chunks, beta near 2
    # no multiple of the chunk, a fast decay, an odd number of heads: one
    # a grid step
    'ragged_fast': (2 * C + 22, 3, 0.5, 1.0),
    'short_slow': (40, 2, 0.999, 0.5)}          # less than a chunk


@pytest.mark.usefixtures('small_kernel_bodies')
@pytest.mark.parametrize('case,path', [
    ('one', 'kernel'), ('one', 'plain'), ('two_beta2', 'kernel'),
    ('ragged_fast', 'kernel'), ('ragged_fast', 'plain'),
    ('short_slow', 'kernel')], indirect=['path'])
def test_gated_delta_rule(case, path):
    length, heads, decay, beta_at = RULE_CASES[case]
    args = _operands(2, length, heads, decay, beta_at)
    _both(_rule(heads), _reference_rule(heads), *args)


@pytest.mark.usefixtures('small_kernel_bodies')
def test_gated_delta_rule_writes_its_statistics(monkeypatch):
    """rows scanned, and the largest magnitude of a state after the last
    row, on both paths."""
    H, L = 2, C + 8
    args = _operands(2, L, H, 0.98, 1.5, seed=7)
    want = max(float(jnp.abs(_reference_rule(H, True)(
        *(x[b] for x in args))[1][-1]).max()) for b in range(2))
    fn = op('GatedDeltaRule', num_heads=H)
    for force in ('0', '1'):
        monkeypatch.setenv('MXTPU_FORCE_PALLAS', force)
        out, stats = fn(*args, jnp.zeros((2,), jnp.float32))
        assert out.shape == (2, L, H * dv) and float(stats[0]) == 2 * L
        np.testing.assert_allclose(float(stats[1]), want, rtol=1e-5)


def _by_head(H, q, k, v, g, beta):
    """pk.delta_chunks' operands from the op's, whole chunks (the last
    padded as pk.delta_rule pads it): q, k, v [B, H, T, D], gamma and
    beta [B, H, n, 1, C]."""
    B, length = g.shape[:2]
    pad = -length % C
    heads = lambda x, D: jnp.pad(                               # noqa: E731
        x.reshape(B, length, H, D).transpose(0, 2, 1, 3),
        ((0, 0), (0, 0), (0, pad), (0, 0)))
    by_chunk = lambda x: jnp.pad(                               # noqa: E731
        x.transpose(0, 2, 1), ((0, 0), (0, 0), (0, pad))).reshape(
            B, H, -1, 1, C)
    return (ref.unit(heads(q, dk), dk ** -0.5), ref.unit(heads(k, dk)),
            heads(v, dv), jnp.cumsum(by_chunk(g), axis=-1), by_chunk(beta))


def _chunks_plain(q, k, v, gamma, beta):
    """pk.delta_chunks in plain jnp with a solve for the inverse: (A, X,
    the chain's six operands)."""
    B, H, rows, _ = q.shape
    mm = lambda spec, a, b: jnp.einsum(spec, a, b,              # noqa: E731
                                       precision='highest')
    q, k, v = (x.reshape(B, H, rows // C, C, -1) for x in (q, k, v))
    gamma, beta = gamma[..., 0, :], beta[..., 0, :]
    i = jnp.arange(C)
    Gamma = jnp.exp(jnp.where(i[:, None] >= i[None, :], gamma[..., :, None]
                              - gamma[..., None, :], -jnp.inf))
    a = jnp.where(i[:, None] > i[None, :], beta[..., :, None]
                  * mm('bhnid,bhnjd->bhnij', k, k) * Gamma, 0.0)
    x = jnp.linalg.inv(jnp.eye(C) + a)
    t, e = x * beta[..., None, :], jnp.exp(gamma)[..., None]
    last = gamma[..., -1:]
    flat = lambda x: x.reshape(B, H, rows, -1)                  # noqa: E731
    return a, x, (
        flat(q * e), flat(k * jnp.exp(last - gamma)[..., None]),
        flat(mm('bhnij,bhnjd->bhnid', t, k * e)),
        flat(mm('bhnij,bhnjd->bhnid', t, v)),
        flat(mm('bhnid,bhnjd->bhnij', q, k) * Gamma),
        jnp.broadcast_to(jnp.exp(last)[..., None],
                         last.shape[:3] + (1, v.shape[-1])))


# (few heads: a grid step takes all of them, and its body is unrolled)
CHUNK_CASES = {'two_beta2': (2 * C, 2, 0.95, 1.9),  # two chunks, beta near 2
               'ragged_fast': (2 * C - 10, 1, 0.5, 1.0)}    # a padded chunk


@pytest.mark.parametrize('case', sorted(CHUNK_CASES))
def test_the_kernels_of_a_chunk_are_its_algebra(case):
    """``delta_rule_solve``'s inverse and ``delta_rule_chunk_fwd``'s six
    results against the jnp form, and ``delta_rule_chunk_bwd``'s five
    cotangents (the inverse's own inside it) against autodiff of that
    form."""
    length, heads, decay, beta_at = CHUNK_CASES[case]
    args = _by_head(heads, *_operands(1, length, heads, decay, beta_at))
    q, k, v, gamma, beta = args
    _close(pk.delta_solve(k, gamma, beta, jnp.float32),
           _chunks_plain(*args)[1], tol=1e-4)
    got, vjp = jax.vjp(pk.delta_chunks, *args)
    want, want_vjp = jax.vjp(lambda *a: _chunks_plain(*a)[2], *args)
    cot = tuple(_rand(90 + at, *o.shape) for at, o in enumerate(want))
    for a, b in zip(got + vjp(cot), want + want_vjp(cot)):
        _close(a, b, tol=1e-4)


@pytest.mark.usefixtures('small_kernel_bodies')
def test_the_state_at_a_chunks_start_is_the_recurrences():
    """Two derivations: the forward kernel's state at the second chunk's
    start is the reference's state after row C - 1 of the recurrence, and
    the second chunk's output follows from it by the chunk's own algebra,
    done here by hand."""
    H = 2
    q, k, v, g, beta = _operands(1, 2 * C, H, 0.97, 1.6, seed=3)
    operands = pk.delta_chunks(*_by_head(H, q, k, v, g, beta))
    o, states, smax = pk.delta_scan_forward(*operands)
    want_o, want_s = _reference_rule(H, True)(q[0], k[0], v[0], g[0],
                                              beta[0])
    assert not np.asarray(states[:, :, 0]).any()
    _close(states[0, :, 1], want_s[C - 1])
    _close(jnp.max(smax, axis=(2, 3))[0],
           jnp.max(jnp.abs(want_s[-1]), axis=(1, 2)))
    # the second chunk by hand, from the handed-on state
    qg, kd, w, u, p, decay = (x[0, :, C:] if x.ndim == 4 else x[0, :, 1]
                              for x in operands)
    S = want_s[C - 1]
    u1 = u - jnp.einsum('hik,hkv->hiv', w, S)
    by_hand = jnp.einsum('hik,hkv->hiv', qg, S) \
        + jnp.einsum('hij,hjv->hiv', p, u1)
    _close(by_hand.transpose(1, 0, 2), want_o[C:])
    _close(o[0, :, C:], by_hand)
    _close(decay * S + jnp.einsum('hik,hiv->hkv', kd, u1), want_s[-1])


@pytest.mark.parametrize('rows', [8, pk._DELTA_SOLVE_ROWS, C])
def test_the_inverse_in_the_kernel_is_a_solve(rows, monkeypatch):
    """With beta near 2 and keys close to one another A's entries are near
    2: ``delta_rule_solve``'s X against float64's inverse of I + A, with
    the diagonal blocks that forward substitution clears at the length
    the program has, at a shorter one (three merges by pairs) and at the
    whole chunk (no merge)."""
    monkeypatch.setattr(pk, '_DELTA_SOLVE_ROWS', rows)
    H = 1
    q, k, v, g, beta = _operands(1, C, H, 0.98, 1.95, seed=5)
    k = k.reshape(1, C, H, dk)[:, :1] + 0.3 * k.reshape(1, C, H, dk)
    _, k, _, gamma, beta = _by_head(H, q, k.reshape(1, C, H * dk), v, g, beta)
    a = np.asarray(_chunks_plain(k, k, k, gamma, beta)[0], np.float64)
    assert np.abs(a).max() > 1.5
    _close(pk.delta_solve(k, gamma, beta, jnp.float32),
           np.linalg.inv(np.eye(C) + a).astype(np.float32), tol=1e-4)


@pytest.mark.usefixtures('small_kernel_bodies')
@pytest.mark.parametrize('path', ['kernel'], indirect=True)
def test_a_fast_decay_does_not_overflow(path):
    """Gamma is formed from the difference: with 40 a row in the exponent
    the product of exp(gamma_i) and exp(-gamma_j) would be inf times 0."""
    H = 2
    q, k, v, _, beta = _operands(1, C, H, 0.5, 1.0)
    g = jnp.full((1, C, H), -40.0, jnp.float32)
    got = _rule(H)(q, k, v, g, beta)
    assert np.isfinite(np.asarray(got)).all()
    _close(got, _reference_rule(H)(q, k, v, g, beta))


# -- the convolution -------------------------------------------------------------------------

@pytest.mark.parametrize('length,taps,path', [
    (T, 4, 'kernel'), (T, 4, 'plain'), (2, 4, 'kernel'), (300, 4, 'kernel'),
    (T, 2, 'kernel')], indirect=['path'])
def test_short_conv(length, taps, path):
    """Both paths against the reference's shifted sums: a sequence shorter
    than the taps, and one with a row-block boundary inside (the kernels
    take 256 rows a block: the rows before a block and, backward, the next
    block's first rows of dy silu'(c) come from the halo)."""
    x, w = _rand(0, 2, length, 48), _rand(1, 48, taps, scale=0.5)
    _both(op('ShortConv', kernel=taps),
          lambda x, w: jnp.stack([jax.nn.silu(ref.short_conv(x[b], w))
                                  for b in range(x.shape[0])]), x, w)
    with pytest.raises(ValueError, match='ShortConv'):
        op('ShortConv', kernel=taps + 1)(x, w)


# -- what a mirrored linear-attention block keeps --------------------------------------------

LM_IN = dict(data=(1, C + 8), softmax_label=(1, C + 8))


def _linear_block():
    return builder.get_symbol(dict(CFG, num_hidden_layers=1,
                                   layer_types=['linear_attention']))


def _kernels(text):
    return {k: text.count('name=delta_rule_%s' % k)
            for k in ('fwd', 'bwd', 'solve', 'chunk_fwd', 'chunk_bwd')}


@pytest.mark.usefixtures('small_kernel_bodies')
@pytest.mark.parametrize('path', ['kernel'], indirect=True)
def test_the_chain_of_chunks_runs_once_a_direction(path, monkeypatch):
    """The stage keeps the chain's output and the states at the chunks'
    starts (``delta_rule_out``, ``delta_rule_states``): the forward kernel
    is in the step as often as the backward one; under a bare checkpoint
    it, and the solve, run again in the backward pass, and the gradients
    are the same (to rounding: XLA fuses the two programs differently):
    what was kept is what is made again."""
    step, wrt = _training_step(_linear_block(), **LM_IN)
    text = str(jax.make_jaxpr(step)(wrt))
    # (each call is in the text twice: compiled for a TPU, interpreted here)
    kept = _kernels(text)
    assert kept['fwd'] == kept['bwd'] > 0
    assert 'name=delta_rule_states' in text and 'name=delta_rule_out' in text
    outs, grads = jax.jit(step)(wrt)
    cases._bare_checkpoint(monkeypatch)
    step, wrt = _training_step(_linear_block(), **LM_IN)
    assert _kernels(str(jax.make_jaxpr(step)(wrt))) == dict(
        kept, fwd=2 * kept['fwd'], solve=2 * kept['solve'])
    for a, b in zip(outs + grads, sum(jax.jit(step)(wrt), ())):
        _close(a, b, tol=1e-6)


@pytest.mark.usefixtures('small_kernel_bodies')
@pytest.mark.parametrize('path', ['kernel'], indirect=True)
def test_the_solve_runs_once_a_direction(path):
    """The stage keeps the chunks' inverses too (``delta_rule_inverse``):
    ``delta_rule_solve`` is in the step as often as the backward kernels,
    and the stage's second forward of a chunk's state-free part is
    ``delta_rule_chunk_fwd`` alone."""
    step, wrt = _training_step(_linear_block(), **LM_IN)
    text = str(jax.make_jaxpr(step)(wrt))
    kept = _kernels(text)
    assert kept['solve'] == kept['chunk_bwd'] == kept['bwd'] > 0
    assert kept['chunk_fwd'] == 2 * kept['solve']
    assert 'name=delta_rule_inverse' in text


def test_the_nodes_name_their_statistics():
    sym = builder.get_symbol(CFG)
    assert delta_stat_names(sym) == sym.list_auxiliary_states() \
        == ['layer%d_lin_stats' % i for i in range(3)]
