"""Training input: an endless stream of fresh [batch, seqlen] token ids
and labels, uniform over the vocabulary, made on the host from the seed
one step at a time (so the input path is inside the measured loop)."""
import numpy as np


def batches(mix, seed, batch, seqlen, vocab):
    rng = np.random.default_rng([seed, 8])
    while True:
        yield (rng.integers(0, vocab, size=(batch, seqlen)),
               rng.integers(0, vocab, size=(batch, seqlen)))
