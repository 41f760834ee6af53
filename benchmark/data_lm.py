"""Seeded synthetic token streams with something to learn: one first-order
Markov chain over the vocabulary. Every id has a few likely successors
(`SUCCESSORS` of them, with probabilities `PROBS`), drawn from a skewed
popularity (a Zipf law over a seeded permutation of the ids), so that a
model lowers its loss first by the marginal and then by the transitions.
One stream is one document: a step's sequence is a contiguous cut of it,
with no packing and no padding, and its labels are the next tokens."""
import numpy as np

SUCCESSORS = 4
PROBS = (0.55, 0.25, 0.12, 0.08)


def token_pool(seed, tokens, vocab):
    """int32 (tokens + 1,): a chain of `tokens` transitions."""
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    rank = rng.permutation(vocab)
    popularity = 1.0 / (1.0 + np.arange(vocab))
    popularity /= popularity.sum()
    table = rank[rng.choice(vocab, size=(vocab, SUCCESSORS), p=popularity)]
    choice = rng.choice(SUCCESSORS, size=tokens, p=PROBS)
    rows = table.tolist()
    out = [int(rng.integers(vocab))]
    x = out[0]
    for c in choice.tolist():       # the chain is sequential by nature
        x = rows[x][c]
        out.append(x)
    return np.asarray(out, np.int32)


def batch_offset(k, tokens, span):
    """Where sequence number k starts in a pool of `tokens` transitions: a
    rolling offset, so that consecutive steps are different arrays."""
    return (k * 104729) % (tokens - span + 1)


def cut(pool, k, batch, seq_len):
    """(ids, next ids) of step number k, each (batch, seq_len): every row a
    cut of the stream at its own rolling offset, the labels one token on."""
    tokens = len(pool) - 1
    rows = [batch_offset(k * batch + r, tokens, seq_len)
            for r in range(batch)]
    return (np.stack([pool[o:o + seq_len] for o in rows]),
            np.stack([pool[o + 1:o + 1 + seq_len] for o in rows]))
