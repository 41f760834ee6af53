"""Long-context training via sequence parallelism (ring attention).

The long-context story end to end: a causal transformer LM whose
sequence dimension is SHARDED over the mesh's `sp` axis — activations
for a seq-L batch never exist whole on one device; attention runs as
ring attention (K/V blocks rotate around the ring via ppermute,
arXiv:2310.01889) inside the same jitted SPMD train step as dp-sharded
data parallelism.

Trains on a synthetic needle-detection task that REQUIRES long-range
attention: the prediction at the FINAL position is whether a needle
token appeared in the first eighth of the sequence — on the sp mesh
that information lives on a different device, so the gradient path runs
through the rotating K/V ring. Loss at the answer position must beat
the 2-way uniform baseline.

    python train_long_context.py --sp 4 --dp 2 --seq 256 --steps 200
"""
import argparse
import logging
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..', '..'))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from mxnet_tpu.parallel import shard_map

from mxnet_tpu import parallel as par
from mxnet_tpu.parallel.ring_attention import (ring_attention,
                                                striped_attention,
                                                ulysses_attention)


def make_model_fns(vocab, d_model, n_heads, attn='ring'):
    head_dim = d_model // n_heads

    def init(key):
        ks = jax.random.split(key, 7)
        s = d_model ** -0.5
        return {
            'emb': jax.random.normal(ks[0], (vocab, d_model)) * s,
            'wq': jax.random.normal(ks[1], (d_model, d_model)) * s,
            'wk': jax.random.normal(ks[2], (d_model, d_model)) * s,
            'wv': jax.random.normal(ks[3], (d_model, d_model)) * s,
            'wo': jax.random.normal(ks[4], (d_model, d_model)) * s,
            'wf': jax.random.normal(ks[5], (d_model, d_model)) * s,
            'out': jax.random.normal(ks[6], (d_model, vocab)) * s,
        }

    def forward(params, tokens):
        # tokens: (B, L) with B sharded on dp, L sharded on sp
        x = params['emb'][tokens]                       # (B, L, D)
        q = (x @ params['wq']).reshape(*x.shape[:2], n_heads, head_dim)
        k = (x @ params['wk']).reshape(*x.shape[:2], n_heads, head_dim)
        v = (x @ params['wv']).reshape(*x.shape[:2], n_heads, head_dim)
        # ring attention over the sp axis: K/V blocks rotate the ring.
        # 'striped' expects round-robin token layout (see main) and
        # balances the causal load across the ring (arXiv:2311.09431)
        attend = {'ring': ring_attention, 'striped': striped_attention,
                  'ulysses': ulysses_attention}[attn]
        att = attend(q, k, v, axis='sp', causal=True)
        att = att.reshape(*x.shape[:2], d_model)
        x = x + att @ params['wo']
        x = x + jax.nn.relu(x @ params['wf'])           # cheap mixer
        return x @ params['out']                        # (B, L, V)

    return init, forward


def needle_batch(rng, batch, seq, vocab):
    """Needle-in-a-haystack: [... maybe-NEEDLE ...... ASK] — predict
    YES/NO at the final (ASK) position iff the needle token occurred in
    the first eighth of the sequence."""
    NEEDLE, ASK, YES, NO = vocab - 4, vocab - 3, vocab - 2, vocab - 1
    toks = rng.randint(0, vocab - 4, (batch, seq))
    tgts = np.roll(toks, -1, axis=1)
    mask = np.zeros((batch, seq), np.float32)
    for b in range(batch):
        present = rng.rand() < 0.5
        if present:
            toks[b, rng.randint(0, seq // 8)] = NEEDLE
        toks[b, seq - 1] = ASK
        tgts[b, seq - 1] = YES if present else NO
        mask[b, seq - 1] = 1.0
    return toks, tgts, mask


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--dp', type=int, default=2)
    p.add_argument('--sp', type=int, default=4)
    p.add_argument('--seq', type=int, default=256)
    p.add_argument('--batch', type=int, default=16)
    p.add_argument('--vocab', type=int, default=64)
    p.add_argument('--d-model', type=int, default=64)
    p.add_argument('--heads', type=int, default=4)
    p.add_argument('--steps', type=int, default=200)
    p.add_argument('--lr', type=float, default=3e-3)
    p.add_argument('--seed', type=int, default=0)
    p.add_argument('--attn', choices=('ring', 'striped', 'ulysses'),
                   default='ring')
    args = p.parse_args()
    logging.basicConfig(level=logging.INFO)

    if args.attn == 'ulysses' and args.heads % args.sp:
        p.error('--attn ulysses needs --heads divisible by --sp '
                '(all_to_all moves whole heads across the axis)')
    mesh = par.make_mesh({'dp': args.dp, 'sp': args.sp})
    rng = np.random.RandomState(args.seed)
    init, forward = make_model_fns(args.vocab, args.d_model,
                                   args.heads, attn=args.attn)
    params = init(jax.random.PRNGKey(args.seed))

    data_spec = P('dp', 'sp')

    def loss_fn(params, toks, tgts, mask):
        logits = forward(params, toks).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        gold = jnp.take_along_axis(logp, tgts[..., None], -1)[..., 0]
        # masked mean over recall positions only (psum'd across shards)
        num = jax.lax.psum(jnp.sum(-gold * mask), ('dp', 'sp'))
        den = jax.lax.psum(jnp.sum(mask), ('dp', 'sp'))
        return num / jnp.maximum(den, 1.0)

    opt_init, opt_update = par.data_parallel.adam_rule(lr=args.lr)

    def step(state, toks, tgts, mask):
        params, opt, t = state
        loss, grads = jax.value_and_grad(loss_fn)(params, toks, tgts, mask)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.pmean(g, ('dp', 'sp')), grads)
        flat_p, tree = jax.tree_util.tree_flatten(params)
        flat_g = jax.tree_util.tree_leaves(grads)
        new_p, new_o = [], []
        for p_, g_, o_ in zip(flat_p, flat_g, opt):
            p2, o2 = opt_update(p_, g_, o_, t)
            new_p.append(p2)
            new_o.append(o2)
        return (jax.tree_util.tree_unflatten(tree, new_p), tuple(new_o),
                t + 1), loss

    sharded_step = jax.jit(shard_map(
        step, mesh=mesh.mesh,
        in_specs=((P(), P(), P()), data_spec, data_spec, data_spec),
        out_specs=((P(), P(), P()), P()), check_vma=False))
    state = (params,
             tuple(opt_init(p_) for p_ in
                   jax.tree_util.tree_leaves(params)),
             jnp.zeros((), jnp.int32))

    uniform = np.log(2.0)   # YES/NO at the answer position
    if args.attn == 'striped':
        # host-side stripe_layout permutation: position t'*sp + s moves
        # to shard s slot t' (matches parallel.stripe_layout)
        stripe_order = np.concatenate([np.arange(s, args.seq, args.sp)
                                       for s in range(args.sp)])
    first = last = None
    for i in range(args.steps):
        toks, tgts, mask = needle_batch(rng, args.batch, args.seq,
                                        args.vocab)
        if args.attn == 'striped':
            toks, tgts, mask = (toks[:, stripe_order], tgts[:, stripe_order],
                                mask[:, stripe_order])
        state, loss = sharded_step(state, jnp.asarray(toks),
                                   jnp.asarray(tgts), jnp.asarray(mask))
        loss = float(loss)
        if first is None:
            first = loss
        last = loss
        if i % 5 == 0:
            logging.info('step %d needle-loss %.3f (uniform %.3f)', i,
                         loss, uniform)
    logging.info('needle loss %.3f -> %.3f over seq=%d sharded sp=%d',
                 first, last, args.seq, args.sp)
    assert last < 0.7 * uniform, \
        'long-range detection did not learn: %.3f vs uniform %.3f' % (
            last, uniform)
    print('long-context ring-attention training ok: %.3f -> %.3f'
          % (first, last))


if __name__ == '__main__':
    main()
