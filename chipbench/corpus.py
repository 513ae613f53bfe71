"""Traffic generator: Wikipedia-like text, one document per line.

A copy of ``bench.py``'s ``make_lexicon``/``make_corpus``, kept with the
benchmark so that no change to the program can move it. One change: the
lexicon (the "language") is drawn from a fixed seed, and only the
documents come from the run's seed, so the frozen WordPiece vocab in
``data/vocab.txt`` fits the text of every seed alike.

Words are letter-frequency-weighted latin strings with a sprinkle of
accents, digits and CJK, drawn on a Zipf(1.07) rank-frequency curve;
documents have 8-40 sentences of 6-32 words.
"""

import os

import numpy as np

LEXICON_SEED = 0
N_TYPES = 30000
ZIPF_A = 1.07

_ACCENTS = list("éàüñöçåèêôîûáíóúäß")
_CJK = [chr(c) for c in range(0x4E00, 0x4E60)]
_LETTERS = "etaoinshrdlucmfwypvbgkqjxz"
_LETTER_P = np.array([
    12.7, 9.1, 8.2, 7.5, 7.0, 6.7, 6.3, 6.1, 6.0, 4.3, 4.0, 2.8, 2.8, 2.4,
    2.4, 2.4, 2.0, 2.0, 1.9, 1.5, 1.0, 0.8, 0.15, 0.15, 0.15, 0.07])
_LETTER_P = _LETTER_P / _LETTER_P.sum()


def make_lexicon(g, n_types=N_TYPES):
    """Procedural word types (see the module docstring)."""
    lengths = g.integers(2, 13, size=n_types)
    letters = np.array(list(_LETTERS))
    words = []
    for i in range(n_types):
        n = int(lengths[i])
        w = "".join(letters[g.choice(26, size=n, p=_LETTER_P)])
        r = g.random()
        if r < 0.05:  # accented
            pos = int(g.integers(0, n))
            w = (w[:pos] + _ACCENTS[int(g.integers(0, len(_ACCENTS)))]
                 + w[pos + 1:])
        elif r < 0.07:  # digit-bearing (years, measures)
            w = str(int(g.integers(0, 10000))) if g.random() < 0.5 else (
                w + str(int(g.integers(0, 100))))
        elif r < 0.075:  # CJK run
            w = "".join(_CJK[int(g.integers(0, len(_CJK)))]
                        for _ in range(int(g.integers(1, 4))))
        words.append(w)
    return words


def make_corpus(out_root, target_mb, seed, shards=4):
    """Write ``target_mb`` MiB of documents under ``out_root/source`` in
    ``shards`` files; the same seed writes the same bytes. Returns the
    number of bytes written."""
    source = os.path.join(out_root, "source")
    os.makedirs(source)
    lexicon = np.asarray(make_lexicon(np.random.default_rng(LEXICON_SEED)),
                         dtype=object)
    g = np.random.default_rng(seed)
    ranks = np.arange(1, N_TYPES + 1, dtype=np.float64)
    cdf = np.cumsum(1.0 / ranks ** ZIPF_A)
    cdf /= cdf[-1]
    punct = np.array([".", ".", ".", ".", "!", "?"], dtype=object)

    target_bytes = int(target_mb * 1024 * 1024)
    written = 0
    doc_id = 0
    files = [open(os.path.join(source, "{}.txt".format(i)), "w",
                  encoding="utf-8") for i in range(shards)]
    try:
        while written < target_bytes:
            n_sents = int(g.integers(8, 40))
            sent_lens = g.integers(6, 32, size=n_sents)
            total = int(sent_lens.sum())
            doc_words = lexicon[np.searchsorted(cdf, g.random(total))]
            ends = np.cumsum(sent_lens)
            marks = punct[g.integers(0, len(punct), size=n_sents)]
            sents = [" ".join(doc_words[e - n:e]).capitalize() + m
                     for e, n, m in zip(ends.tolist(), sent_lens.tolist(),
                                        marks.tolist())]
            line = "wiki-{} {}\n".format(doc_id, " ".join(sents))
            files[doc_id % shards].write(line)
            written += len(line.encode("utf-8"))
            doc_id += 1
    finally:
        for f in files:
            f.close()
    return written
