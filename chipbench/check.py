"""The comparison that decides ``correct``.

Two parts, both independent of the program's code:

- the loader's output: every batch of the window is checked for the
  layout a BERT batch must have ([CLS] A [SEP] B [SEP], a prefix
  attention mask, token types, labels only on real tokens), and a sample
  of its rows, drawn from the seed, must each be a sample that the
  balanced shards hold, masks and labels included (read with pyarrow);
- the train step: the first three steps against the plain float32
  reference from the same weights and batches. Compared: each step's
  loss and global gradient norm, the first gradient leaf by leaf as the
  optimizer got it (Adam's first moment after one step over 1 - b1), and
  the parameters' change after the last compared step, leaf by leaf, each
  by its worst leaf and by its median leaf.

A leaf's gap is the gap between its two norms, over the reference's norm
of that leaf or the median leaf's, whichever is larger. Leaves whose
first raw gradient in the reference is under a thousandth of the median
leaf's (a key bias under the softmax) move by round-off alone and are left
out of the change.
"""

import glob
import os

import numpy as np

IGNORE = -1
SAMPLE_ROWS = 2048


def shard_keys(shard_dir):
    """The set of samples the balanced shards hold, as hashable keys."""
    import pyarrow.parquet as pq
    cols = ["A_ids", "B_ids", "is_random_next", "masked_lm_positions_ids",
            "masked_lm_label_ids"]
    keys = set()
    for path in sorted(glob.glob(os.path.join(shard_dir, "shard-*"))):
        t = pq.read_table(path, columns=cols)
        a, b, nsp, pos, lab = (t.column(c).to_pylist() for c in cols)
        for row in zip(a, b, nsp, pos, lab):
            keys.add((tuple(row[0]), tuple(row[1]), bool(row[2]),
                      tuple(row[3]), tuple(row[4])))
    return keys


def layout_faults(batch, cls_id, sep_id):
    """Rows of a host batch that break the BERT batch layout (vectorised
    over the batch). Returns (bad row mask, per-row B start)."""
    ids = batch["input_ids"]
    typ = batch["token_type_ids"]
    am = batch["attention_mask"]
    lab = batch["labels"]
    n_rows, width = ids.shape
    col = np.arange(width)[None, :]
    n = am.sum(axis=1)
    b_start = np.where(typ.any(axis=1), np.argmax(typ > 0, axis=1), n)
    rows = np.arange(n_rows)
    bad = (am != (col < n[:, None])).any(axis=1)
    bad |= (typ != ((col >= b_start[:, None]) & (col < n[:, None]))).any(1)
    bad |= ids[:, 0] != cls_id
    bad |= ids[rows, np.clip(b_start - 1, 0, width - 1)] != sep_id
    bad |= ids[rows, np.clip(n - 1, 0, width - 1)] != sep_id
    bad |= ((col >= n[:, None]) & (ids != 0)).any(axis=1)
    bad |= (b_start < 2) | (b_start >= n - 1)
    special = (col == 0) | (col == (b_start - 1)[:, None]) | \
        (col >= (n - 1)[:, None])
    bad |= ((lab != IGNORE) & special).any(axis=1)
    return bad, b_start


def row_key(batch, r, b_start):
    ids = batch["input_ids"][r]
    n = int(batch["attention_mask"][r].sum())
    lab = batch["labels"][r]
    pos = np.flatnonzero(lab != IGNORE)
    return (tuple(ids[1:b_start - 1].tolist()),
            tuple(ids[b_start:n - 1].tolist()),
            bool(batch["next_sentence_labels"][r]),
            tuple(pos.tolist()), tuple(lab[pos].tolist()))


def loader_check(batches, shard_dir, cls_id, sep_id, seed):
    """(rows breaking the layout, sampled rows not in the shards, rows
    sampled) over a list of host batches."""
    bad_rows, layout = 0, []
    for b in batches:
        bad, b_start = layout_faults(b, cls_id, sep_id)
        bad_rows += int(bad.sum())
        layout.append((bad, b_start))
    rows = [(i, r) for i, b in enumerate(batches)
            for r in range(len(b["input_ids"])) if not layout[i][0][r]]
    g = np.random.default_rng(seed)
    pick = g.permutation(len(rows))[:SAMPLE_ROWS]
    keys = shard_keys(shard_dir)
    missing = 0
    for k in pick.tolist():
        i, r = rows[k]
        if row_key(batches[i], r, int(layout[i][1][r])) not in keys:
            missing += 1
    return bad_rows, missing, len(pick)


def leaf_gaps(prog, ref, include=None):
    """{leaf: gap} between two {name: norm} dicts (see module doc)."""
    names = sorted(ref)
    if include is not None:
        names = [n for n in names if include[n]]
    floor = float(np.median([ref[n] for n in sorted(ref)]))
    return {n: abs(float(prog[n]) - float(ref[n])) / max(float(ref[n]), floor)
            for n in names}


def train_numbers(prog, ref):
    """The compared train-step numbers of a program (or control) reading
    against the reference reading. Each reading is a dict with
    ``losses``, ``grad_norms`` (per step), ``grad1`` and ``change``
    ({leaf: norm}); the reference's also has ``raw_grad1``."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    gp, gr = np.asarray(prog["grad_norms"]), np.asarray(ref["grad_norms"])
    raw = ref["raw_grad1"]
    med = float(np.median(list(raw.values())))
    moving = {n: raw[n] >= 1e-3 * med for n in raw}
    g1 = leaf_gaps(prog["grad1"], ref["grad1"])
    ch = leaf_gaps(prog["change"], ref["change"], include=moving)
    g1_leaf, ch_leaf = max(g1, key=g1.get), max(ch, key=ch.get)
    return {
        "loss_rel_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_norm_rel_gap": float(np.max(np.abs(gp - gr) / gr)),
        "grad1_leaf_gap": g1[g1_leaf],
        "grad1_median_leaf_gap": float(np.median(list(g1.values()))),
        "change_leaf_gap": ch[ch_leaf],
        "change_median_leaf_gap": float(np.median(list(ch.values()))),
    }, {"grad1_leaf": g1_leaf, "change_leaf": ch_leaf,
        "leaves_left_out_of_change": sorted(n for n in moving
                                            if not moving[n])}
