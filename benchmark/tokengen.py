"""The token traffic's data, from the run's seed: documents of lognormal
length and Zipf-distributed token ids, separated by an end-of-text id and
packed end to end into fixed-length sequences with no padding; each
sequence's targets are its ids shifted left by one (the next token, across
document boundaries too, as packed pre-training data is). Written as the
HDF5 token file the program's ``HDF5_DATA`` layer reads: 2-D int32 ``data``
and ``label``, and the text file that lists it.

Seeded through ``numpy.random.default_rng`` (any non-negative seed, 2**31
and over included). Same seed, same bytes.
"""

from __future__ import annotations

import os

import numpy as np


def zipf_ids(rng, n: int, vocab: int, exponent: float,
             reserved: int) -> np.ndarray:
    """``n`` ids over [0, vocab) without ``reserved`` (the end-of-text id),
    rank r drawn with probability proportional to r^-exponent."""
    ranks = np.arange(1, vocab, dtype=np.float64)          # vocab - 1 ids
    cdf = np.cumsum(ranks ** -exponent)
    ids = np.searchsorted(cdf, rng.random(n) * cdf[-1]).astype(np.int64)
    return np.where(ids >= reserved, ids + 1, ids)


def packed_sequences(seed: int, sequences: int, seq_len: int, vocab: int,
                     mix: dict) -> dict:
    """``{"data", "label"}`` (sequences, seq_len) int32 and the document
    lengths that went in."""
    rng = np.random.default_rng([int(seed), 25])
    need = sequences * seq_len + 1        # one more: the last target
    eot = int(mix["end_of_text_id"])
    lengths, have = [], 0
    while have < need:
        n = int(np.clip(round(rng.lognormal(np.log(mix["doc_len_median"]),
                                            mix["doc_len_sigma"])),
                        mix["doc_len_min"], mix["doc_len_max"]))
        lengths.append(n)
        have += n + 1                     # the document and its separator
    stream = zipf_ids(rng, have, vocab, mix["zipf_exponent"], eot)
    ends = np.cumsum(np.asarray(lengths) + 1) - 1
    stream[ends] = eot
    stream = stream[:need].astype(np.int32)
    return {"data": stream[:-1].reshape(sequences, seq_len),
            "label": stream[1:].reshape(sequences, seq_len),
            "doc_lengths": lengths}


def build_token_file(out_dir: str, seed: int, sequences: int, seq_len: int,
                     vocab: int, mix: dict) -> dict:
    """Write ``tokens.h5`` + ``tokens.txt`` under ``out_dir`` (rebuilt every
    run: it is 64 KB a step at 4096 positions). Returns the paths and what
    the file holds."""
    import h5py
    os.makedirs(out_dir, exist_ok=True)
    made = packed_sequences(seed, sequences, seq_len, vocab, mix)
    h5_path = os.path.join(out_dir, "tokens.h5")
    with h5py.File(h5_path, "w") as h:
        h.create_dataset("data", data=made["data"])
        h.create_dataset("label", data=made["label"])
    list_path = os.path.join(out_dir, "tokens.txt")
    with open(list_path, "w") as f:
        f.write(h5_path + "\n")
    lengths = np.asarray(made["doc_lengths"])
    return {"source": list_path, "sequences": sequences, "seq_len": seq_len,
            "documents": int(len(lengths)),
            "doc_len_median": float(np.median(lengths)),
            "end_of_text_share": float(np.mean(made["data"]
                                               == mix["end_of_text_id"]))}
