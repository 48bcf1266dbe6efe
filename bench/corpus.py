"""Seeded word corpora for the benchmark, and their plain shortlex reference.

A corpus is ``n`` lowercase words of 1..15 letters, packed big-endian into
(n, 4) uint32 lanes with zero padding: the layout of ``core/packing.py``, in
which unsigned lane order is byte order. Each token's length is drawn from
the configuration's English word-length distribution, then its word by Zipf
rank among the distinct words of that length in a vocabulary. The
vocabulary's letters follow English letter frequencies; its size and the
Zipf exponent are fitted to the published word counts of Shakespeare's
canon (the configuration's ``sources``), and
``tests/test_perfbench_corpus.py`` holds the generator to those counts. Nothing loops per word: the vocabulary
is drawn one length class at a time and the tokens with one inverse-CDF
search per class.

The reference is NumPy's ``lexsort`` on ``(length, lane 0, ..., lane 3)``,
and imports nothing of the program under test.
"""

from __future__ import annotations

import numpy as np

LANES = 4
ALPHABET = 26
# lengths with at most this many possible words are drawn from all of
# them, without replacement; longer ones by drawing and dropping repeats
ENUMERATE_UP_TO = ALPHABET ** 3


def seed_sequence(seed: int, *stream: int) -> np.random.SeedSequence:
    """The seed sequence of one stream of ``seed``: any whole number,
    negative or past 64 bits included."""
    return np.random.SeedSequence([seed & (2**64 - 1), *stream])


def _pack(letters: np.ndarray) -> np.ndarray:
    """(n, length) letter indices 0..25 to (n, 4) big-endian uint32, zero
    padded."""
    chars = np.zeros((letters.shape[0], 4 * LANES), np.uint32)
    chars[:, :letters.shape[1]] = letters + ord("a")
    b = chars.reshape(chars.shape[0], LANES, 4)
    return (b[..., 0] << 24) | (b[..., 1] << 16) | (b[..., 2] << 8) | b[..., 3]


def _distinct_words(rng, v: int, length: int, letter_p) -> np.ndarray:
    """``v`` distinct words of ``length`` letters as (v, length) letter
    indices, each word as likely as the product of its letters'
    frequencies, in the order drawn."""
    if ALPHABET ** length <= ENUMERATE_UP_TO:
        every = (np.arange(ALPHABET ** length)[:, None]
                 // ALPHABET ** np.arange(length - 1, -1, -1)) % ALPHABET
        weight = np.prod(letter_p[every], axis=1)
        return every[rng.choice(len(every), size=v, replace=False,
                                p=weight / weight.sum())]
    words = np.empty((0, length), np.int64)
    while len(words) < v:
        drawn = np.concatenate([words, rng.choice(
            ALPHABET, size=(v - len(words), length), p=letter_p)])
        _, first = np.unique(drawn, axis=0, return_index=True)
        words = drawn[np.sort(first)]
    return words


def make_vocabulary(config: dict, rng) -> list:
    """Per word length ``l`` (index ``l - 1``), the packed (v_l, 4) distinct
    words of that length in Zipf rank order: ``vocabulary * p_l`` of them,
    at least one and at most the ``26**l`` that exist."""
    p = np.asarray(config["length_distribution"], np.float64)
    p = p / p.sum()
    letter_p = np.asarray(config["letter_frequencies"], np.float64)
    letter_p = letter_p / letter_p.sum()
    out = []
    for length, p_l in enumerate(p, start=1):
        v = int(min(max(1, round(config["vocabulary"] * p_l)),
                    ALPHABET ** length))
        out.append(_pack(_distinct_words(rng, v, length, letter_p)))
    return out


def make_corpus(config: dict, vocab: list, n: int, rng):
    """``n`` tokens: each token's length drawn from the length distribution,
    then its word by Zipf rank within that length's vocabulary. Returns
    ``(keys (n, 4) uint32, lengths (n,) int32)``."""
    p = np.asarray(config["length_distribution"], np.float64)
    p = p / p.sum()
    s = config["zipf_exponent"]
    lengths = rng.choice(np.arange(1, len(p) + 1), size=n, p=p).astype(
        np.int32)
    u = rng.random(n)
    keys = np.empty((n, LANES), np.uint32)
    for length, words in enumerate(vocab, start=1):
        at = np.flatnonzero(lengths == length)
        cdf = np.cumsum(1.0 / np.arange(1, words.shape[0] + 1) ** s)
        rank = np.searchsorted(cdf, u[at] * cdf[-1], side="right")
        keys[at] = words[np.minimum(rank, words.shape[0] - 1)]
    return keys, lengths


def make_pool(config: dict, seed: int, size: int) -> list:
    """``size`` distinct corpora of ``config["words_per_job"]`` words, all
    over one vocabulary, every one of them a function of ``seed`` alone."""
    vocab = make_vocabulary(config, np.random.default_rng(
        seed_sequence(seed, 0)))
    return [make_corpus(config, vocab, config["words_per_job"],
                        np.random.default_rng(seed_sequence(seed, 1, i)))
            for i in range(size)]


def reference(keys: np.ndarray, lengths: np.ndarray):
    """Shortlex order of a packed corpus: length first, then the key lanes
    (``np.lexsort`` takes its primary key last). Returns the sorted
    ``(lengths, keys)``."""
    order = np.lexsort(tuple(keys[:, i] for i in reversed(
        range(keys.shape[1]))) + (lengths,))
    return lengths[order], keys[order]


def mismatched_rows(got_lengths, got_keys, want_lengths, want_keys) -> int:
    """Rows of a sort's output that differ from the reference; an output of
    another shape counts every reference row as wrong."""
    got_lengths = np.asarray(got_lengths)
    got_keys = np.asarray(got_keys)
    if (got_keys.shape != want_keys.shape
            or got_lengths.shape != want_lengths.shape):
        return int(want_lengths.shape[0])
    return int(np.count_nonzero((got_lengths != want_lengths)
                                | np.any(got_keys != want_keys, axis=1)))
