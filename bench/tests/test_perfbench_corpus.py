"""The seeded corpus generator and the NumPy shortlex reference."""

import json
import os

import numpy as np
import pytest

import corpus

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                       "configs")


def _config(name="paper-ds1", **words):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        config = json.load(f)
    config.update(words)
    return config


def _unpack(keys):
    raw = np.asarray(keys).astype(">u4").view(np.uint8).reshape(len(keys), -1)
    return [bytes(row).rstrip(b"\0").decode() for row in raw]


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 2**64 + 3, -7])
def test_same_seed_same_corpus_and_seeds_differ(seed):
    config = _config(words_per_job=2000)
    a = corpus.make_pool(config, seed, 3)
    b = corpus.make_pool(config, seed, 3)
    c = corpus.make_pool(config, seed + 1, 3)
    for (ka, la), (kb, lb) in zip(a, b):
        assert np.array_equal(ka, kb) and np.array_equal(la, lb)
    assert not np.array_equal(a[0][0], c[0][0])
    assert not np.array_equal(a[0][0], a[1][0])   # the pool is distinct
    assert a[0][0].shape == (2000, 4) and a[0][0].dtype == np.uint32


def test_words_are_lowercase_with_their_lengths():
    keys, lengths = corpus.make_pool(_config(words_per_job=5000), 1, 1)[0]
    words = _unpack(keys)
    assert [len(w) for w in words] == lengths.tolist()
    assert all(w.isalpha() and w.islower() for w in words)
    assert 1 <= lengths.min() and lengths.max() <= 15


def _counts(keys):
    """How often each distinct word occurs."""
    rows = np.ascontiguousarray(keys).view(np.dtype((np.void, 16))).ravel()
    return np.unique(rows, return_counts=True)[1]


def _within(value, lo, hi, rel):
    return lo * (1 - rel) <= value <= hi * (1 + rel)


def test_lengths_follow_the_distribution_and_words_repeat():
    config = _config("paper-ds2")
    keys, lengths = corpus.make_pool(config, 3, 1)[0]
    assert len(lengths) == 230_000
    p = np.asarray(config["length_distribution"])
    share = np.bincount(lengths, minlength=16)[1:] / len(lengths)
    assert np.allclose(share, p / p.sum(), atol=0.005)
    assert 1_000 < len(_counts(keys)) < config["vocabulary"]


def test_the_vocabulary_is_distinct_words_in_english_letters():
    config = _config()
    vocab = corpus.make_vocabulary(config, np.random.default_rng(8))
    p = np.asarray(config["length_distribution"])
    for length, words in enumerate(vocab, start=1):
        want = min(round(config["vocabulary"] * p[length - 1] / p.sum()),
                   26 ** length)
        assert words.shape == (want, 4) and len(_counts(words)) == want
    letters = np.concatenate([np.frombuffer("".join(_unpack(w)).encode(),
                                            np.uint8) for w in vocab[3:]])
    share = np.bincount(letters - ord("a"), minlength=26) / len(letters)
    f = np.asarray(config["letter_frequencies"])
    assert np.allclose(share, f / f.sum(), atol=0.003)


@pytest.mark.parametrize("name", ["paper-ds1", "paper-ds2"])
def test_a_canon_sized_draw_has_shakespeares_word_counts(name):
    """As many words as Shakespeare's canon, drawn from the configuration,
    hold about as many distinct words, and words used once, as the canon
    (Efron and Thisted, 1976)."""
    config = _config(name)
    known = config["published"]
    vocab = corpus.make_vocabulary(config, np.random.default_rng(9))
    keys, _ = corpus.make_corpus(config, vocab, known["canon_words"],
                                 np.random.default_rng(10))
    counts = _counts(keys)
    assert _within(len(counts), known["canon_distinct_words"],
                   known["canon_distinct_words"], 0.03)
    once = known["canon_words_seen_1_to_10_times"][0]
    assert _within(np.count_nonzero(counts == 1), once, once, 0.03)


def _sample_of_the_canon(known, n):
    """The distinct words, and the words used once, that a random sample
    of ``n`` of the canon's words holds by the published counts: each
    word seen ``x`` times in the canon is in it with probability
    ``1 - (1 - t)**x``, ``t = n / canon_words``. Only words seen up to 10
    times are counted one by one; the rest, seen 11 times or more, give
    the bounds."""
    t = n / known["canon_words"]
    n_x = np.asarray(known["canon_words_seen_1_to_10_times"], np.float64)
    x = np.arange(1, len(n_x) + 1)
    rest = known["canon_distinct_words"] - n_x.sum()
    types = np.sum(n_x * (1 - (1 - t) ** x))
    once = np.sum(n_x * x * t * (1 - t) ** (x - 1))
    many = np.arange(11, 100_000)
    once_at_most = np.max(many * t * (1 - t) ** (many - 1))
    return ((types + rest * (1 - (1 - t) ** 11), types + rest),
            (once, once + rest * once_at_most))


@pytest.mark.parametrize("name", ["paper-ds1", "paper-ds2"])
def test_a_job_holds_the_words_of_a_sample_of_the_canon(name):
    config = _config(name)
    (types_lo, types_hi), (once_lo, once_hi) = _sample_of_the_canon(
        config["published"], config["words_per_job"])
    keys, _ = corpus.make_pool(config, 2**31 + 11, 1)[0]
    counts = _counts(keys)
    assert _within(len(counts), types_lo, types_hi, 0.03)
    assert _within(np.count_nonzero(counts == 1), once_lo, once_hi, 0.03)


@pytest.mark.parametrize("name", ["paper-ds1", "paper-ds2"])
def test_bytes_per_word_fit_the_source_text(name):
    """English tokens average 4.79 letters; with one separator each, a
    job's words may not need more bytes than the paper's text file holds."""
    config = _config(name)
    p = np.asarray(config["length_distribution"])
    p = p / p.sum()
    length = np.arange(1, len(p) + 1)
    mean = np.sum(p * length)
    assert abs(mean / config["published"]["mean_letters_per_word"] - 1) < 0.01
    _, lengths = corpus.make_pool(config, 12, 1)[0]
    error = np.sqrt(np.sum(p * (length - mean) ** 2) / len(lengths))
    assert abs(lengths.mean() - mean) < 4 * error
    assert (lengths.mean() + 1) * len(lengths) <= config["source_text_bytes"]
