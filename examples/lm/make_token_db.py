#!/usr/bin/env python3
"""Write a token file for the HDF5_DATA layer of a token model
(examples/lm/olmoe_1b_7b_train.prototxt): 2-D int32 ``data`` (sequences x
seq_len ids) and ``label`` (the next id at every position), plus the text
file that lists it.

    python examples/lm/make_token_db.py                   # synthetic tokens
    python examples/lm/make_token_db.py --ids corpus.npy  # your tokenizer's

``--ids`` takes a 1-D array of token ids (documents already joined by your
end-of-text id) and packs it end to end, no padding. Without it the ids are
synthetic: Zipf over the vocabulary, an end-of-text id after documents of
lognormal length (median 512).
"""

import argparse
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=os.path.join(HERE, "olmoe_tokens"))
    ap.add_argument("--ids", default="", help="1-D .npy of token ids")
    ap.add_argument("--seq_len", type=int, default=4096)
    ap.add_argument("--sequences", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=50304)
    ap.add_argument("--eot", type=int, default=50279)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    need = args.sequences * args.seq_len + 1
    if args.ids:
        stream = np.load(args.ids).reshape(-1)
        n = (len(stream) - 1) // args.seq_len
        if n < 1:
            raise SystemExit(f"{args.ids}: fewer than {args.seq_len + 1} ids")
        need = n * args.seq_len + 1
    else:
        rng = np.random.default_rng(args.seed)
        weights = np.arange(1, args.vocab + 1, dtype=np.float64) ** -1.0
        weights[args.eot] = 0.0
        stream = rng.choice(args.vocab, size=need, p=weights / weights.sum())
        ends = np.cumsum(np.clip(rng.lognormal(np.log(512), 1.2, need // 16),
                                 16, args.seq_len).astype(np.int64) + 1)
        stream[ends[ends < need]] = args.eot
    stream = np.asarray(stream[:need], np.int32)

    import h5py
    with h5py.File(args.out + ".h5", "w") as h:
        h["data"] = stream[:-1].reshape(-1, args.seq_len)
        h["label"] = stream[1:].reshape(-1, args.seq_len)
    with open(args.out + ".txt", "w") as f:
        f.write(args.out + ".h5\n")
    print(f"{(need - 1) // args.seq_len} sequences of {args.seq_len} -> "
          f"{args.out}.h5, listed in {args.out}.txt")


if __name__ == "__main__":
    main()
