"""BERT pretraining data loader: decode, collation, dynamic masking, factory.

Reference parity: lddl/torch/bert.py (and the torch_mp variant's loss-mask
output). Differences by design, for TPU:

- Batches are numpy (int32) dicts; the training step moves them to devices
  as globally-sharded jax.Arrays via loader/sharding.py.
- ``fixed_seq_lengths`` pads every batch of a bin to that bin's boundary
  instead of the batch max: a *bounded set of static shapes* means a
  bounded number of XLA compilations (the TPU version of the reference's
  Tensor-Core alignment trick, lddl/torch/bert.py:91-96 — which we also
  keep for the unbinned path via ``sequence_length_alignment``).
- Dynamic masking is vectorized numpy on deterministic per-(epoch, dp
  group, worker) streams — identical across TP/PP peers, like everything
  else in the loader.
"""

import numpy as np

from ..utils.fs import (
    deserialize_np_array,
    get_all_bin_ids,
    get_all_parquets_under,
    get_file_paths_for_bin_id,
    get_generation_of_path,
)
from ..utils.logging import DatasetLogger
from .dataloader import Binned, DataLoader, count_dropout_mask_tiles
from .datasets import (ParquetDataset, annotate_quarantine,
                       verified_shard_paths)


def generation_gate_filter(root, paths):
    """Apply the generation pickup gate: the root ``.manifest.json``'s
    ``__meta__`` generation is the LAST thing the ingest publisher
    writes, so shards under gen dirs newer than it are excluded even if
    their files already exist (a generation mid-publish is never
    visible). A directory with no generation meta (classic offline
    output) gates nothing and follows whatever is on disk. Returns
    (filtered_paths, gate). The startup file list and every epoch-
    boundary refresh go through this one filter."""
    from ..resilience.integrity import read_manifest
    manifest = read_manifest(root)
    meta = manifest.get("__meta__") if manifest else None
    gate = meta.get("generation") if isinstance(meta, dict) else None
    if gate is not None:
        paths = [p for p in paths
                 if get_generation_of_path(root, p) <= gate]
    return paths, gate


def packed_shape_of_dir(path, file_paths=None):
    """(pack_seq_length, pack_max_per_row) of an offline-packed shard
    directory, or None. The root ``.manifest.json``'s ``__meta__.packed``
    entry (written by build_manifest off every shard's footer) is
    authoritative; manifest-less or meta-less directories sniff one
    shard's footer metadata instead — detection must work for raw
    preprocess output too, not only published datasets."""
    from ..resilience.integrity import read_manifest
    manifest = read_manifest(path)
    meta = (manifest.get("__meta__") if manifest else None) or {}
    packed = meta.get("packed")
    if isinstance(packed, dict):
        try:
            return (int(packed["pack_seq_length"]),
                    int(packed["pack_max_per_row"]))
        except (KeyError, TypeError, ValueError):
            return None
    if file_paths is None:
        from ..utils.fs import get_all_parquets_under
        file_paths = get_all_parquets_under(path)
    if file_paths:
        from ..preprocess.packing import pack_shape_of_parquet
        return pack_shape_of_parquet(sorted(file_paths)[0])
    return None


class GenerationSnapshot:
    """One gate + directory-listing read shared by every bin's follower
    within one epoch boundary (keyed by the boundary's epoch number), so
    a generation publish landing between two bins' refreshes cannot give
    a single epoch a generation-mixed view — a fresh loader started at
    that epoch index must reproduce its batches exactly."""

    def __init__(self, root):
        self.root = root
        self._key = None
        self._value = None

    def get(self, key):
        if key is None or key != self._key:
            self._value = generation_gate_filter(
                self.root, get_all_parquets_under(self.root))
            self._key = key
        return self._value


class GenerationFollower:
    """Picklable refresh callable for generation-aware loading: returns
    the currently-published, verified shard list for one dataset (one bin
    or the unbinned whole; see generation_gate_filter for the visibility
    rule)."""

    def __init__(self, root, bin_id=None, on_corrupt=None, snapshot=None):
        self.root = root
        self.bin_id = bin_id
        self.on_corrupt = on_corrupt
        self.snapshot = snapshot or GenerationSnapshot(root)
        self.last_gate = None
        self._epoch_key = None
        self._last = None  # (gated bin paths, verified result)

    def set_epoch_key(self, key):
        """Called by the dataset right before the refresh with the epoch
        boundary's number — the snapshot cache key every bin shares."""
        self._epoch_key = key

    def __call__(self):
        paths, gate = self.snapshot.get(self._epoch_key)
        self.last_gate = gate
        # Bin-filter BEFORE verifying (bin_id=None serves the unbinned
        # dataset, not "all bins"), and serve an unchanged set from the
        # memo: integrity verification is a startup/pickup contract, not
        # a per-epoch CRC re-scan of the whole directory.
        paths = get_file_paths_for_bin_id(paths, self.bin_id)
        if self._last is not None and self._last[0] == paths:
            return list(self._last[1])
        verified = verified_shard_paths(self.root, paths,
                                        on_corrupt=self.on_corrupt)
        self._last = (paths, verified)
        return list(verified)


def _list_views(col):
    """(values, offsets) numpy views of an Arrow ``list<int32>`` column —
    the values buffer is referenced zero-copy, so per-row slices are views
    into the shard's decoded Arrow memory, never per-row Python objects."""
    lens = col.value_lengths().to_numpy(zero_copy_only=False)
    values = col.flatten().to_numpy(zero_copy_only=True)
    offsets = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    return values, offsets


def _decode_columnar(b, names):
    """Schema-v2 fast path: one zero-copy buffer grab per column, then
    per-row ndarray views (no string materialization, no per-token work —
    the collate consumes the id views directly)."""
    flat_a, off_a = _list_views(b.column("A_ids"))
    flat_b, off_b = _list_views(b.column("B_ids"))
    rn = b.column("is_random_next").to_numpy(zero_copy_only=False)
    n = len(rn)
    if "masked_lm_positions_ids" in names:
        pos_v, pos_off = _list_views(b.column("masked_lm_positions_ids"))
        lab_v, lab_off = _list_views(b.column("masked_lm_label_ids"))
        for i in range(n):
            yield (flat_a[off_a[i]:off_a[i + 1]],
                   flat_b[off_b[i]:off_b[i + 1]], rn[i],
                   pos_v[pos_off[i]:pos_off[i + 1]],
                   lab_v[lab_off[i]:lab_off[i + 1]])
    else:
        for i in range(n):
            yield (flat_a[off_a[i]:off_a[i + 1]],
                   flat_b[off_b[i]:off_b[i + 1]], rn[i])


class PackedRow(tuple):
    """One offline-packed shard row decoded to views:
    ``(ids, tok3, content, samp, mlm2)``. ``ids`` is the stored
    fully-interleaved row content ([CLS]/[SEP] baked in at pack time);
    ``tok3`` stacks the boundary-derived per-token arrays
    ``[segments, position_ids, token_type]`` and ``samp`` the per-sample
    arrays ``[a_lens, b_lens, off, nsp(, mask_lens)]`` — both
    precomputed ONCE per decode chunk, stacked so a row is FIVE slices
    and a batch is four axis-1 concatenates; ``content`` is the
    content-vs-special bool mask (dynamic masking), ``mlm2`` stacks
    ``[positions, labels]`` (ROW-relative positions as stored; None for
    dynamic-masking corpora). The named properties unpack the stacks. A
    distinct type (not a bare tuple) so collates can assert they were
    wired to the decode path they expect."""

    __slots__ = ()

    ids = property(lambda s: s[0])
    seg = property(lambda s: s[1][0])
    pos = property(lambda s: s[1][1])
    typ = property(lambda s: s[1][2])
    content = property(lambda s: s[2])
    a_lens = property(lambda s: s[3][0])
    b_lens = property(lambda s: s[3][1])
    off = property(lambda s: s[3][2])
    nsp = property(lambda s: s[3][3])
    mask_lens = property(lambda s: s[3][4] if len(s[3]) > 4 else None)
    mlm_pos = property(lambda s: s[4][0] if s[4] is not None else None)
    mlm_labels = property(lambda s: s[4][1] if s[4] is not None else None)


# Decode-chunk token budget: the boundary-derived per-token arrays are
# materialized per chunk (not per shard-sized record batch — row groups
# can span a whole shard), so buffered rows keep at most
# ~shuffle_buffer_size + chunk rows' worth of derived arrays alive.
_DECODE_CHUNK_TOKENS = 1 << 20


def _decode_prepacked(b, names):
    """Offline-packed fast path: one zero-copy buffer grab per column,
    the boundary-derived per-token arrays computed ONCE per chunk of
    rows (vectorized over thousands of rows, amortizing numpy dispatch),
    then per-ROW views — each yielded sample is one already-packed
    training row; no repacking and no per-sample work ever happens at
    load time."""
    ids_v, ids_off = _list_views(b.column("input_ids"))
    al_v, al_off = _list_views(b.column("pack_a_lens"))
    nsp_v, _ = _list_views(b.column("pack_nsp"))
    bl_v, _ = _list_views(b.column("pack_b_lens"))
    static = "pack_mask_lens" in names
    if static:
        pos_v, pos_off = _list_views(b.column("masked_lm_positions_ids"))
        lab_v, _ = _list_views(b.column("masked_lm_label_ids"))
        ml_v, _ = _list_views(b.column("pack_mask_lens"))
    n = b.num_rows
    row = 0
    aranges = BertCollate._concat_aranges
    while row < n:
        end = row + 1
        while end < n and ids_off[end + 1] - ids_off[row] \
                <= _DECODE_CHUNK_TOKENS:
            end += 1
        # Sample-flat slices for rows [row, end); all derived arrays are
        # chunk-relative, computed in one vectorized pass, and STACKED
        # (per-token x3, per-sample x4|5, mlm x2) so each row is five
        # slices and a batch is four axis-1 concatenates.
        s0, s1 = int(al_off[row]), int(al_off[end])
        al = al_v[s0:s1].astype(np.int64)
        bl = bl_v[s0:s1].astype(np.int64)
        spr = (al_off[row:end + 1] - s0).astype(np.int64)
        samples_per_row = np.diff(spr)
        tot = al + bl + 3
        slot = aranges(samples_per_row)
        pos64 = aranges(tot)
        tok3 = np.empty((3, len(pos64)), dtype=np.int32)
        tok3[0] = np.repeat(slot + 1, tot)                  # segments
        tok3[1] = pos64                                     # position_ids
        tok3[2] = pos64 >= np.repeat(2 + al, tot)           # token_type
        content = ((pos64 != 0)
                   & (pos64 != np.repeat(1 + al, tot))
                   & (pos64 != np.repeat(tot - 1, tot)))
        cum = np.cumsum(tot) - tot              # token start per sample
        samp = np.empty((5 if static else 4, len(al)), dtype=np.int32)
        samp[0] = al_v[s0:s1]
        samp[1] = bl_v[s0:s1]
        samp[2] = cum - np.repeat(cum[spr[:-1]], samples_per_row)
        samp[3] = nsp_v[s0:s1]
        if static:
            samp[4] = ml_v[s0:s1]
            m0 = int(pos_off[row])
            mlm2 = np.empty((2, int(pos_off[end]) - m0), dtype=np.int32)
            mlm2[0] = pos_v[m0:int(pos_off[end])]
            mlm2[1] = lab_v[m0:int(pos_off[end])]
        # Slice bounds as plain ints, materialized once per chunk (a few
        # per ROW, never per token): numpy scalar extraction inside the
        # per-row loop costs ~10x a list index.
        idsb = ids_off[row:end + 1].tolist()  # lddl: disable=python-hot-loop
        trow = (ids_off[row:end + 1] - ids_off[row]).tolist()  # lddl: disable=python-hot-loop
        sprl = spr.tolist()  # lddl: disable=python-hot-loop
        if static:
            mb = (pos_off[row:end + 1] - m0).tolist()  # lddl: disable=python-hot-loop
        for i in range(end - row):
            mlm = mlm2[:, mb[i]:mb[i + 1]] if static else None
            yield PackedRow((
                ids_v[idsb[i]:idsb[i + 1]],
                tok3[:, trow[i]:trow[i + 1]], content[trow[i]:trow[i + 1]],
                samp[:, sprl[i]:sprl[i + 1]], mlm))
        row = end


def decode_record_batch(b):
    """Yield sample tuples from a parquet RecordBatch:
    (A, B, is_random_next[, masked_lm_positions, masked_lm_labels]).

    Schema v2 shards (``A_ids`` present) decode to int32 ndarray views
    over the batch's flat token-id buffers; schema v1 decodes to the
    original Python strings. Offline-packed shards (``pack_a_lens``
    present) decode one PackedRow of views per parquet row. Selection is
    per-shard, so directories mixing both schemas stream correctly (and
    byte-identically — the collate normalizes)."""
    from .. import observability as obs
    names = b.schema.names
    if "pack_a_lens" in names:
        if obs.enabled():
            obs.inc("loader_decode_packed_batches_total")
        yield from _decode_prepacked(b, names)
        return
    if "A_ids" in names:
        if obs.enabled():
            obs.inc("loader_decode_columnar_batches_total")
        yield from _decode_columnar(b, names)
        return
    if obs.enabled():
        obs.inc("loader_decode_legacy_batches_total")
    static = "masked_lm_positions" in names
    # Legacy v1 text path: per-row Python strings are the shard format.
    b = b.to_pydict()  # lddl: disable=python-hot-loop
    if static:
        for row in zip(b["A"], b["B"], b["is_random_next"],
                       b["masked_lm_positions"], b["masked_lm_labels"]):
            yield row
    else:
        for row in zip(b["A"], b["B"], b["is_random_next"]):
            yield row


class BertCollate:
    """samples -> encoded numpy batch dict.

    Static masking (5-tuples): emits ``labels`` from the stored positions.
    Dynamic masking (3-tuples): masks on the fly with the worker stream.
    Output keys: input_ids, token_type_ids, attention_mask,
    next_sentence_labels, labels, masked_lm_positions-mask (``loss_mask``,
    the torch_mp extra output for Megatron-style loss,
    ref lddl/torch_mp/bert.py:103-105).
    """

    needs_rng = True

    def __init__(self, tokenizer, sequence_length_alignment=8,
                 fixed_seq_length=None, ignore_index=-1, mlm_prob=0.15,
                 emit_loss_mask=False):
        self._tokenizer = tokenizer
        self._align = sequence_length_alignment
        self._fixed_seq_length = fixed_seq_length
        self._ignore_index = ignore_index
        self._mlm_prob = mlm_prob
        self._emit_loss_mask = emit_loss_mask
        self._mask_id = tokenizer.convert_tokens_to_ids("[MASK]")
        self._cls_id = tokenizer.convert_tokens_to_ids("[CLS]")
        self._sep_id = tokenizer.convert_tokens_to_ids("[SEP]")
        self._vocab_size = len(tokenizer)
        # One dict lookup per token beats a per-sample HF call; shards store
        # tokens this tokenizer produced, so misses (-> unk) are impossible
        # in practice but keep convert_tokens_to_ids semantics anyway.
        self._vocab = dict(tokenizer.get_vocab())
        self._unk_id = tokenizer.convert_tokens_to_ids(
            tokenizer.unk_token or "[UNK]")

    def _batch_seq_len(self, lens):
        longest = max(lens)
        if self._fixed_seq_length is not None:
            if longest > self._fixed_seq_length:
                raise ValueError(
                    "sample of {} tokens exceeds fixed_seq_length {}".format(
                        longest, self._fixed_seq_length))
            return self._fixed_seq_length
        from ..ops.packing import round_up
        return round_up(longest, self._align)

    def _token_ids_and_lens(self, seqs):
        """One flat id array + per-item lengths. Items are int32 token-id
        ndarrays (schema-v2 shards: used as-is, zero per-token work) or
        space-joined token strings (schema-v1: split + vocab lookup),
        freely mixed when a directory holds shards of both schemas."""
        n_str = sum(isinstance(s, str) for s in seqs)
        if n_str == 0:
            # Columnar fast path: lengths off the views, ONE C-level
            # concatenation for the flat batch buffer.
            lens = np.fromiter(map(len, seqs), dtype=np.int64,
                               count=len(seqs))
            flat = (np.concatenate(seqs) if len(seqs)
                    else np.zeros(0, dtype=np.int32))
            return np.ascontiguousarray(flat, dtype=np.int32), lens
        vocab_get = self._vocab.get
        unk = self._unk_id
        if n_str == len(seqs):
            # Pure v1: single bulk pass, dict lookups only. Per-token
            # Python iteration is inherent to the text schema (baselined).
            token_lists = [t.split() for t in seqs]
            lens = np.fromiter((len(t) for t in token_lists),
                               dtype=np.int64, count=len(token_lists))
            flat = np.fromiter(
                (vocab_get(t, unk) for ts in token_lists for t in ts),
                dtype=np.int32, count=int(lens.sum()))
            return flat, lens
        # Mixed v1/v2 batch (shards of both schemas in one directory):
        # normalize the strings, then concatenate like the fast path.
        arrs = [s if not isinstance(s, str) else
                np.fromiter((vocab_get(t, unk) for t in s.split()),
                            dtype=np.int32)
                for s in seqs]
        lens = np.fromiter(map(len, arrs), dtype=np.int64, count=len(arrs))
        return np.concatenate(arrs).astype(np.int32, copy=False), lens

    @staticmethod
    def _positions_and_lens(samples):
        """Flat masked-lm positions + per-sample counts, as ONE batched
        decode: schema-v2 rows carry int32 ndarray views (already sliced
        from one Arrow buffer), schema-v1 rows carry serialize_np_array
        bytes (decoded per row — the v1 format is row-serialized)."""
        pos_list = [s[3] if not isinstance(s[3], (bytes, bytearray))
                    else deserialize_np_array(s[3])
                    for s in samples]
        lens = np.fromiter(map(len, pos_list), dtype=np.int64,
                           count=len(pos_list))
        flat = (np.concatenate(pos_list).astype(np.int64, copy=False)
                if pos_list else np.zeros(0, dtype=np.int64))
        return flat, lens

    @staticmethod
    def _concat_aranges(lens):
        """[arange(l) for l in lens] concatenated, without a Python loop."""
        total = int(lens.sum())
        if total == 0:
            return np.zeros(0, dtype=np.int64)
        starts = np.cumsum(lens) - lens
        return np.arange(total, dtype=np.int64) - np.repeat(starts, lens)

    def __call__(self, samples, g=None):
        n = len(samples)
        static = len(samples[0]) == 5
        flat_a, lens_a = self._token_ids_and_lens([s[0] for s in samples])
        flat_b, lens_b = self._token_ids_and_lens([s[1] for s in samples])
        ends = lens_a + lens_b + 3
        seq_len = self._batch_seq_len([int(ends.max())])

        rows = np.arange(n, dtype=np.int64)
        col = np.arange(seq_len, dtype=np.int64)[None, :]
        # Flat scatter of the A and B segments: row offsets repeated per
        # token + a concatenated per-row arange gives every target slot.
        idx_a = (np.repeat(rows, lens_a) * seq_len
                 + 1 + self._concat_aranges(lens_a))
        idx_b = (np.repeat(rows * seq_len + 2 + lens_a, lens_b)
                 + self._concat_aranges(lens_b))

        input_ids = np.zeros((n, seq_len), dtype=np.int32)
        input_ids[:, 0] = self._cls_id
        input_ids.flat[idx_a] = flat_a
        input_ids.flat[idx_b] = flat_b
        input_ids[rows, 1 + lens_a] = self._sep_id
        input_ids[rows, ends - 1] = self._sep_id

        token_type_ids = ((col >= (2 + lens_a)[:, None])
                          & (col < ends[:, None])).astype(np.int32)
        attention_mask = (col < ends[:, None]).astype(np.int32)

        labels = np.full((n, seq_len), self._ignore_index, dtype=np.int32)
        if static:
            flat_pos, pos_lens = self._positions_and_lens(samples)
            flat_labels, lens_m = self._token_ids_and_lens(
                [s[4] for s in samples])
            if not np.array_equal(pos_lens, lens_m):
                raise ValueError(
                    "masked_lm_positions/masked_lm_labels length mismatch "
                    "in sample(s) {}".format(
                        # error path only -- lddl: disable=python-hot-loop
                        np.flatnonzero(pos_lens != lens_m).tolist()))
            labels[np.repeat(rows, lens_m), flat_pos] = flat_labels
        else:
            if g is None:
                raise ValueError("dynamic masking needs a worker RNG")
            # Non-special positions eligible for masking.
            special_tokens_mask = np.ones((n, seq_len), dtype=bool)
            special_tokens_mask.flat[idx_a] = False
            special_tokens_mask.flat[idx_b] = False
            input_ids, labels = self._mask_tokens(
                input_ids, special_tokens_mask, g)

        batch = {
            "input_ids": input_ids,
            "token_type_ids": token_type_ids,
            "attention_mask": attention_mask,
            "next_sentence_labels": np.asarray(
                [int(s[2]) for s in samples], dtype=np.int32),
            "labels": labels,
        }
        if self._emit_loss_mask:
            batch["loss_mask"] = (labels != self._ignore_index).astype(np.int32)
        return batch

    def _mask_tokens(self, input_ids, special_tokens_mask, g):
        """Vectorized HF-style dynamic masking: select ~mlm_prob of
        non-special tokens; of those 80% -> [MASK], 10% -> random token,
        10% -> unchanged. (ref: lddl/torch/bert.py:152-196)"""
        shape = input_ids.shape
        masked = (g.random(shape) < self._mlm_prob) & ~special_tokens_mask
        labels = np.where(masked, input_ids, self._ignore_index).astype(np.int32)
        r = g.random(shape)
        out = input_ids.copy()
        out[masked & (r < 0.8)] = self._mask_id
        random_sel = masked & (r >= 0.8) & (r < 0.9)
        random_words = g.integers(0, self._vocab_size, shape, dtype=np.int32)
        out[random_sel] = random_words[random_sel]
        return out, labels


class BertPackedCollate(BertCollate):
    """samples + packed layout -> encoded packed batch (sequence packing,
    ops/packing.py): several samples per fixed-length row, block-diagonal
    attention via per-token ``segments``, per-sample ``position_ids``
    restart, per-sample [CLS] columns in ``cls_positions`` and NSP labels
    [R, P] padded with ignore_index. Rows are always exactly
    ``pack_seq_length`` wide — ONE static shape for the whole run."""

    def __init__(self, tokenizer, pack_seq_length, pack_rows, pack_max_per_row,
                 ignore_index=-1, mlm_prob=0.15, emit_loss_mask=False):
        super().__init__(tokenizer, fixed_seq_length=pack_seq_length,
                         ignore_index=ignore_index, mlm_prob=mlm_prob,
                         emit_loss_mask=emit_loss_mask)
        self._rows = pack_rows
        self._max_per_row = pack_max_per_row

    def __call__(self, layout_rows, samples, g=None):
        return self._encode_packed(layout_rows, samples, g, self._rows)

    def _encode_packed(self, layout_rows, samples, g, R):
        """The packed scatter encode for ``R`` output rows — shared by the
        load-time packer (R = configured pack_rows) and the offline
        prepacked collate (R = rows in this batch). ``R`` is a parameter,
        not instance state: collates are shared across worker threads."""
        from ..ops.packing import packed_layout_arrays
        L, P = self._fixed_seq_length, self._max_per_row
        n = len(samples)
        static = len(samples[0]) == 5
        layout = packed_layout_arrays(layout_rows, L, P)
        if layout["n_rows"] > R or n != len(layout["row_of"]):
            raise ValueError("layout/sample mismatch: {} rows > {} or "
                             "{} != {}".format(layout["n_rows"], R, n,
                                               len(layout["row_of"])))

        flat_a, lens_a = self._token_ids_and_lens([s[0] for s in samples])
        flat_b, lens_b = self._token_ids_and_lens([s[1] for s in samples])
        totals = lens_a + lens_b + 3
        row_of, offset_of = layout["row_of"], layout["offset_of"]
        slot_of = layout["slot_of"]

        base = row_of * L + offset_of                   # flat start per sample
        idx_a = np.repeat(base + 1, lens_a) + self._concat_aranges(lens_a)
        idx_b = (np.repeat(base + 2 + lens_a, lens_b)
                 + self._concat_aranges(lens_b))
        idx_all = np.repeat(base, totals) + self._concat_aranges(totals)

        input_ids = np.zeros((R, L), dtype=np.int32)
        input_ids.flat[idx_a] = flat_a
        input_ids.flat[idx_b] = flat_b
        input_ids.flat[base] = self._cls_id
        input_ids.flat[base + 1 + lens_a] = self._sep_id
        input_ids.flat[base + totals - 1] = self._sep_id

        token_type_ids = np.zeros((R, L), dtype=np.int32)
        # type 1 spans B plus its trailing [SEP], like the unpacked collate.
        idx_b_ext = (np.repeat(base + 2 + lens_a, lens_b + 1)
                     + self._concat_aranges(lens_b + 1))
        token_type_ids.flat[idx_b_ext] = 1

        attention_mask = np.zeros((R, L), dtype=np.int32)
        attention_mask.flat[idx_all] = 1
        segments = np.zeros((R, L), dtype=np.int32)
        segments.flat[idx_all] = np.repeat(slot_of + 1, totals)
        position_ids = np.zeros((R, L), dtype=np.int32)
        position_ids.flat[idx_all] = self._concat_aranges(totals)

        cls_positions = np.zeros((R, P), dtype=np.int32)
        nsp = np.full((R, P), self._ignore_index, dtype=np.int32)
        cls_positions[row_of, slot_of] = offset_of
        nsp[row_of, slot_of] = np.asarray([int(s[2]) for s in samples],
                                          dtype=np.int32)

        labels = np.full((R, L), self._ignore_index, dtype=np.int32)
        if static:
            flat_pos, _ = self._positions_and_lens(samples)
            flat_labels, lens_m = self._token_ids_and_lens(
                [s[4] for s in samples])
            labels.flat[np.repeat(base, lens_m) + flat_pos] = flat_labels
        else:
            if g is None:
                raise ValueError("dynamic masking needs a worker RNG")
            special = np.ones((R, L), dtype=bool)
            special.flat[idx_a] = False
            special.flat[idx_b] = False
            input_ids, labels = self._mask_tokens(input_ids, special, g)

        batch = {
            "input_ids": input_ids,
            "token_type_ids": token_type_ids,
            "attention_mask": attention_mask,
            "segments": segments,
            "position_ids": position_ids,
            "cls_positions": cls_positions,
            "next_sentence_labels": nsp,
            "labels": labels,
        }
        if self._emit_loss_mask:
            batch["loss_mask"] = (labels != self._ignore_index).astype(
                np.int32)
        stats = {"pad_tokens": int(layout["pad_tokens"]
                                   + (R - layout["n_rows"]) * L),
                 "total_tokens": R * L, "n_samples": n}
        return batch, stats


class BertPrepackedCollate(BertPackedCollate):
    """Collate for OFFLINE-packed shards: each input sample is one
    pre-packed row (a PackedRow of zero-copy views from
    decode_record_batch). The FFD packing happened at preprocess time,
    so this encode is FULLY vectorized: the per-row flat buffers
    concatenate once per column (R arrays, not one per sample) and every
    layout quantity — row/slot/offset per sample — derives from the
    stored boundary columns with numpy arithmetic; no per-sample Python
    exists anywhere on the path. Batches are exactly
    ``len(rows) x pack_seq_length``; a full batch always has the one
    static shape, like the load-time packer's output."""

    def __init__(self, tokenizer, pack_seq_length, pack_max_per_row,
                 ignore_index=-1, mlm_prob=0.15, emit_loss_mask=False):
        super().__init__(tokenizer, pack_seq_length, pack_rows=1,
                         pack_max_per_row=pack_max_per_row,
                         ignore_index=ignore_index, mlm_prob=mlm_prob,
                         emit_loss_mask=emit_loss_mask)

    def __call__(self, rows, g=None):
        if not rows or not isinstance(rows[0], PackedRow):
            raise TypeError(
                "BertPrepackedCollate consumes PackedRow samples from "
                "offline-packed shards; got {} — is this directory "
                "actually packed?".format(type(rows[0]).__name__
                                          if rows else "an empty batch"))
        static = rows[0][4] is not None
        L, P, R = self._fixed_seq_length, self._max_per_row, len(rows)

        # Four axis-1 concatenates over R precomputed row views, one
        # index build, then one flat scatter per output array.
        ids_rows = [r[0] for r in rows]
        used = np.fromiter(map(len, ids_rows), dtype=np.int64, count=R)
        bases = np.arange(R, dtype=np.int64) * L
        idx_all = np.repeat(bases, used) + self._concat_aranges(used)
        tok3 = np.concatenate([r[1] for r in rows], axis=1)
        samp = np.concatenate([r[3] for r in rows], axis=1)

        input_ids = np.zeros((R, L), dtype=np.int32)
        input_ids.flat[idx_all] = np.concatenate(ids_rows)
        # The three per-token planes land in ONE fancy-index assignment
        # (their batch dict entries are views of one backing array), and
        # the attention mask needs no scatter at all: packed rows fill a
        # PREFIX of each row, so it is a broadcast compare against the
        # per-row used count.
        out3 = np.zeros((3, R, L), dtype=np.int32)
        out3.reshape(3, R * L)[:, idx_all] = tok3
        segments, position_ids, token_type_ids = out3
        attention_mask = (np.arange(L, dtype=np.int64)[None, :]
                          < used[:, None]).astype(np.int32)

        samples_per_row = np.fromiter(
            (r[3].shape[1] for r in rows), dtype=np.int64, count=R)
        row_of = np.repeat(np.arange(R, dtype=np.int64), samples_per_row)
        slot_of = self._concat_aranges(samples_per_row)
        cls_positions = np.zeros((R, P), dtype=np.int32)
        nsp = np.full((R, P), self._ignore_index, dtype=np.int32)
        cls_positions[row_of, slot_of] = samp[2]
        nsp[row_of, slot_of] = samp[3]

        labels = np.full((R, L), self._ignore_index, dtype=np.int32)
        if static:
            mlm2 = np.concatenate([r[4] for r in rows], axis=1)
            mask_counts = np.fromiter(
                (r[4].shape[1] for r in rows), dtype=np.int64, count=R)
            labels.flat[np.repeat(bases, mask_counts) + mlm2[0]] = mlm2[1]
        else:
            if g is None:
                raise ValueError("dynamic masking needs a worker RNG")
            special = np.ones((R, L), dtype=bool)
            special.flat[idx_all] = ~np.concatenate(
                [r[2] for r in rows])
            input_ids, labels = self._mask_tokens(input_ids, special, g)

        batch = {
            "input_ids": input_ids,
            "token_type_ids": token_type_ids,
            "attention_mask": attention_mask,
            "segments": segments,
            "position_ids": position_ids,
            "cls_positions": cls_positions,
            "next_sentence_labels": nsp,
            "labels": labels,
        }
        if self._emit_loss_mask:
            batch["loss_mask"] = (labels != self._ignore_index).astype(
                np.int32)
        return batch


class PackedBertLoader:
    """Streams raw samples from an inner DataLoader through a
    StreamPacker, emitting packed batches of exactly ``pack_rows`` x
    ``pack_seq_length``. Packing is deterministic (first-fit in stream
    order) and carries leftover samples across batch boundaries, so no
    sample is dropped; the final partial batch pads with empty rows."""

    _PACK_RNG_TAG = 0xACED  # dynamic-masking stream domain for packed mode

    def __init__(self, inner, collate, pack_seq_length, pack_rows,
                 pack_max_per_row, pack_horizon=None):
        from ..ops.packing import StreamPacker
        self._inner = inner
        self._collate = collate
        self._L = pack_seq_length
        self._R = pack_rows
        self._P = pack_max_per_row
        self._horizon = pack_horizon
        self._StreamPacker = StreamPacker
        # Cumulative packing efficiency (reset each epoch): pad_ratio =
        # pad_tokens / total_tokens over the emitted batches.
        self.pad_tokens = 0
        self.total_tokens = 0
        self.n_samples = 0

    @property
    def pad_ratio(self):
        return self.pad_tokens / max(self.total_tokens, 1)

    # Collate encodes run on a small thread pool (numpy scatter work,
    # largely GIL-releasing): layout assignment stays serial-deterministic,
    # encode order is preserved by yielding futures FIFO.
    _COLLATE_THREADS = 2

    def __iter__(self):
        import collections
        import concurrent.futures as cf

        from ..utils import rng as lrng
        ds = self._inner.dataset
        inner_it = iter(self._inner)   # advances the epoch
        packer = self._StreamPacker(self._L, self._R, self._P,
                                    horizon=self._horizon)
        store = {}                     # global ordinal -> sample
        self.pad_tokens = self.total_tokens = self.n_samples = 0
        pool = cf.ThreadPoolExecutor(max_workers=self._COLLATE_THREADS)
        inflight = collections.deque()
        batch_idx = 0

        def submit(rows):
            nonlocal batch_idx
            if not rows:
                return
            # Relabel global ordinals to batch-local 0..n-1 (collate
            # contract) in stream order, and pull their samples.
            ordinals = sorted(o for row in rows for o, _ in row)
            local = {o: i for i, o in enumerate(ordinals)}
            rows_local = [[(local[o], length) for o, length in row]
                          for row in rows]
            samples = [store.pop(o) for o in ordinals]
            # Per-BATCH masking stream (not one shared generator): collates
            # run concurrently on the pool, and interleaved draws from a
            # shared stream would be schedule-dependent.
            g = lrng.sample_rng(ds.base_seed, self._PACK_RNG_TAG, ds.epoch,
                                ds.dp_rank, batch_idx)
            batch_idx += 1
            inflight.append(pool.submit(self._collate, rows_local, samples,
                                        g=g))

        from .. import observability as obs
        obs_on = obs.enabled()

        def drain(block):
            while inflight and (block
                                or len(inflight) > self._COLLATE_THREADS):
                batch, stats = inflight.popleft().result()
                self.pad_tokens += stats["pad_tokens"]
                self.total_tokens += stats["total_tokens"]
                self.n_samples += stats["n_samples"]
                if obs_on:
                    # Packed batches bypass DataLoader's collate metering;
                    # account the paper's padding-efficiency quantity here
                    # from the packer's own layout stats.
                    obs.inc("loader_real_tokens_total",
                            stats["total_tokens"] - stats["pad_tokens"])
                    obs.inc("loader_padded_slots_total",
                            stats["total_tokens"])
                    obs.set_gauge("loader_padding_efficiency",
                                  1.0 - self.pad_ratio)
                    count_dropout_mask_tiles(
                        obs.registry().counter(
                            "attention_dropout_mask_tiles_total"),
                        batch["attention_mask"])
                yield batch

        def seg_len(v):
            # v2 samples carry id ndarrays (len = token count directly);
            # v1 carries space-joined token strings.
            return len(v) if not isinstance(v, str) else len(v.split())

        def sample_len(s):
            return seg_len(s[0]) + seg_len(s[1]) + 3

        try:
            for raw_batch in inner_it:
                # (ds.epoch advanced at the iterator's first yield, before
                # any submit can run)
                for sample in raw_batch:
                    length = sample_len(sample)
                    ordinal = packer.add(length)
                    if ordinal is None:
                        submit(packer.emit_fullest())
                        yield from drain(block=False)
                        ordinal = packer.add(length)
                        assert ordinal is not None
                    store[ordinal] = sample
            while packer.open_rows:
                submit(packer.emit_fullest())
            yield from drain(block=True)
            assert not store
        finally:
            pool.shutdown(wait=False, cancel_futures=True)


class BertPretrainBinned(Binned):

    def _get_batch_size(self, batch):
        # Encoded batches are dicts; return_raw_samples batches are lists.
        if isinstance(batch, dict):
            return len(batch["input_ids"])
        return len(batch)


def get_bert_pretrain_data_loader(
    path,
    dp_rank=0,
    num_dp_groups=1,
    batch_size=64,
    num_workers=1,
    shuffle_buffer_size=16384,
    shuffle_buffer_warmup_factor=16,
    tokenizer=None,
    vocab_file=None,
    tokenizer_name=None,
    sequence_length_alignment=8,
    fixed_seq_lengths=None,
    ignore_index=-1,
    mlm_prob=0.15,
    emit_loss_mask=False,
    base_seed=12345,
    start_epoch=0,
    log_dir=None,
    log_level=None,
    return_raw_samples=False,
    prefetch=2,
    comm=None,
    pack_seq_length=None,
    pack_rows=None,
    pack_max_per_row=8,
    pack_horizon=None,
    pack_allow_uneven_epochs=False,
    worker_mode="thread",
    on_corrupt=None,
    follow_generations=False,
):
    """Build the BERT pretraining loader over balanced shards at ``path``.

    ``follow_generations=True`` serves a streaming-ingestion directory as
    a growing dataset: at every epoch boundary the loader re-reads the
    root manifest's generation gate and picks up newly published
    ``gen-<NNNN>/`` shards without a restart (mid-epoch publishes wait
    for the boundary; see ParquetDataset.maybe_refresh). Off by default —
    classic directories behave exactly as before.

    ``on_corrupt`` sets the startup shard-integrity policy ("fail" |
    "quarantine"; None defers to LDDL_TPU_ON_CORRUPT then "fail") — shards
    are checked against the ``.manifest.json`` their producer published;
    quarantine excludes corrupt shards loudly and continues on the rest.

    Auto-detects binned vs unbinned from the shard filenames and static vs
    dynamic masking from the parquet schema
    (ref: lddl/torch/bert.py:199-413). For TPU static shapes pass
    ``fixed_seq_lengths``: an int (unbinned) or a list with one padded
    length per bin.

    Sequence packing (``pack_seq_length`` + ``pack_rows``): several short
    samples share each fixed-length row with block-diagonal attention —
    batches gain ``segments``/``position_ids``/``cls_positions`` keys, NSP
    labels become [rows, pack_max_per_row], and the consumer is
    models.BertForPreTrainingPacked. Packing subsumes binning (every row
    is exactly pack_seq_length wide), so it requires unbinned shards.

    **Offline-packed directories** (preprocessed with
    ``pack_seq_length=...`` — see preprocess/packing.py) are detected
    automatically from the manifest's ``__meta__.packed`` entry (or a
    footer sniff) and stream their pre-packed rows zero-copy: no load-
    time packing runs at all, the stored row shape is authoritative
    (``pack_seq_length``, if passed, must match; ``pack_rows`` — default
    ``batch_size`` — sets rows per batch), and the batch contract is the
    packed one above. The greedy load-time packer remains the fallback
    for unpacked directories.

    ``dp_rank``/``num_dp_groups`` name the data-parallel group of this
    process — derive them from a device mesh with
    ``lddl_tpu.loader.process_dp_info(mesh)``. All processes in the same
    group receive identical batches (ref: lddl/torch_mp/bert.py:203-211).

    Shard I/O: worker streams acquire shards through the loader shard
    I/O pipeline (loader/shardcache.py) — StorageBackend-routed reads
    with depth-K read-ahead prefetch (``LDDL_TPU_LOADER_PREFETCH_SHARDS``),
    a generation-keyed read-through shard cache
    (``LDDL_TPU_LOADER_CACHE_BYTES``), and decode-ahead. Batch bytes are
    identical with the pipeline on or off; set both knobs to 0 for the
    fully synchronous pre-pipeline path.
    """
    import logging
    if tokenizer is None:
        from ..preprocess.tokenizer import get_tokenizer
        tokenizer = get_tokenizer(vocab_file=vocab_file,
                                  pretrained_model_name=tokenizer_name)
    logger = DatasetLogger(
        log_dir=log_dir,
        log_level=log_level if log_level is not None else logging.WARNING,
        rank=dp_rank,
    )
    file_paths = get_all_parquets_under(path)
    if not file_paths:
        raise ValueError("no parquet shards under {}".format(path))
    if follow_generations:
        # The initial set obeys the same pickup gate a refresh does, so
        # a generation mid-publish at startup is excluded consistently.
        file_paths, _ = generation_gate_filter(path, file_paths)
    n_before = len(file_paths)
    file_paths = verified_shard_paths(path, file_paths,
                                      on_corrupt=on_corrupt, logger=logger,
                                      comm=comm)
    n_quarantined = n_before - len(file_paths)
    try:
        bin_ids = get_all_bin_ids(file_paths)
    except ValueError as e:
        if n_quarantined:
            # Quarantine swallowed a whole bin: point the operator at the
            # corrupt shards just logged, not at the preprocessor.
            raise annotate_quarantine(e, n_quarantined) from e
        raise

    packed_shape = packed_shape_of_dir(path, file_paths)
    if packed_shape is not None:
        # OFFLINE-packed directory: every parquet row is an already-
        # packed training row, so the loader is a plain zero-copy row
        # stream + scatter encode — the greedy load-time pack loop below
        # never runs (it stays only as the fallback for unpacked dirs).
        L, P = packed_shape
        if pack_seq_length is not None and int(pack_seq_length) != L:
            raise ValueError(
                "shards under {} were packed offline at pack_seq_length="
                "{}, which the stored rows fix; requested {}".format(
                    path, L, pack_seq_length))
        if bin_ids:
            raise ValueError("offline-packed shards cannot be binned")
        if return_raw_samples:
            raise ValueError(
                "return_raw_samples over offline-packed shards is not "
                "supported (rows are packed training rows, not samples)")
        if fixed_seq_lengths is not None:
            raise ValueError(
                "offline-packed shards fix the row width at {}; "
                "fixed_seq_lengths does not apply".format(L))
        # Rows per batch: pack_rows when given (API parity with the
        # load-time packer), else the ordinary batch_size. Unlike the
        # stream packer, batch COUNTS are row-arithmetic (balanced ±1
        # shards), so multi-dp-group epochs stay lockstep — no
        # pack_allow_uneven_epochs needed.
        rows = int(pack_rows) if pack_rows is not None else int(batch_size)
        gen_snapshot = GenerationSnapshot(path) if follow_generations \
            else None
        try:
            dataset = ParquetDataset(
                file_paths,
                base_seed=base_seed,
                start_epoch=start_epoch,
                dp_rank=dp_rank,
                num_dp_groups=num_dp_groups,
                num_workers=num_workers,
                shuffle_buffer_size=shuffle_buffer_size,
                shuffle_buffer_warmup_factor=shuffle_buffer_warmup_factor,
                decode_record_batch=decode_record_batch,
                comm=comm,
                logger=logger,
                refresh=(GenerationFollower(path, on_corrupt=on_corrupt,
                                            snapshot=gen_snapshot)
                         if follow_generations else None),
            )
        except ValueError as e:
            if n_quarantined:
                raise annotate_quarantine(e, n_quarantined) from e
            raise
        return DataLoader(
            dataset,
            rows,
            collate_fn=BertPrepackedCollate(
                tokenizer, L, P, ignore_index=ignore_index,
                mlm_prob=mlm_prob, emit_loss_mask=emit_loss_mask),
            prefetch=prefetch,
            worker_mode=worker_mode,
        )

    packing = pack_seq_length is not None or pack_rows is not None
    if packing:
        if pack_seq_length is None or pack_rows is None:
            raise ValueError("packing needs BOTH pack_seq_length and "
                             "pack_rows")
        if num_dp_groups > 1 and not pack_allow_uneven_epochs:
            # Packed batch boundaries depend on each group's length mix,
            # so per-epoch batch COUNTS can differ by a few across dp
            # groups — a lockstep loop would deadlock in a collective at
            # the shortest group's end. Until a synchronized packed epoch
            # exists, the caller must bound steps itself (e.g.
            # itertools.islice to an allreduce-min of batch counts) and
            # acknowledge that with the override flag.
            raise ValueError(
                "sequence packing with num_dp_groups > 1 yields uneven "
                "per-group batch counts; pass "
                "pack_allow_uneven_epochs=True and bound your step loop "
                "(e.g. islice to the min batch count across groups)")
        if bin_ids:
            raise ValueError(
                "packing requires unbinned shards (rows are always exactly "
                "pack_seq_length wide, which subsumes binning); preprocess "
                "without --bin-size")
        if return_raw_samples:
            raise ValueError("return_raw_samples and packing are exclusive")

    # One snapshot for the whole loader: every bin's follower reads the
    # gate + listing from the same per-epoch-keyed cache.
    gen_snapshot = GenerationSnapshot(path) if follow_generations else None

    def make_dataset(paths, transform=None, bin_id=None):
        try:
            return ParquetDataset(
                paths,
                base_seed=base_seed,
                start_epoch=start_epoch,
                dp_rank=dp_rank,
                num_dp_groups=num_dp_groups,
                num_workers=num_workers,
                shuffle_buffer_size=shuffle_buffer_size,
                shuffle_buffer_warmup_factor=shuffle_buffer_warmup_factor,
                decode_record_batch=decode_record_batch,
                transform=transform,
                comm=comm,
                logger=logger,
                refresh=(GenerationFollower(path, bin_id=bin_id,
                                            on_corrupt=on_corrupt,
                                            snapshot=gen_snapshot)
                         if follow_generations else None),
            )
        except ValueError as e:
            # Divisibility/balance errors after a quarantine must name
            # the quarantine, not (only) the shard/dp-group arithmetic.
            if n_quarantined:
                raise annotate_quarantine(e, n_quarantined) from e
            raise

    def make_collate(fixed_seq_length):
        if return_raw_samples:
            return None
        return BertCollate(
            tokenizer,
            sequence_length_alignment=sequence_length_alignment,
            fixed_seq_length=fixed_seq_length,
            ignore_index=ignore_index,
            mlm_prob=mlm_prob,
            emit_loss_mask=emit_loss_mask,
        )

    if bin_ids:
        if fixed_seq_lengths is not None:
            if len(fixed_seq_lengths) != len(bin_ids):
                raise ValueError(
                    "fixed_seq_lengths has {} entries for {} bins".format(
                        len(fixed_seq_lengths), len(bin_ids)))
        else:
            fixed_seq_lengths = [None] * len(bin_ids)
        loaders = [
            DataLoader(
                make_dataset(get_file_paths_for_bin_id(file_paths, b),
                             bin_id=b),
                batch_size,
                collate_fn=make_collate(fixed_seq_lengths[b]),
                prefetch=prefetch,
                worker_mode=worker_mode,
            ) for b in bin_ids
        ]
        return BertPretrainBinned(loaders,
                                  base_seed=base_seed,
                                  start_epoch=start_epoch,
                                  logger=logger)
    if packing:
        inner = DataLoader(make_dataset(file_paths), batch_size,
                           collate_fn=None, prefetch=prefetch,
                           worker_mode=worker_mode)
        return PackedBertLoader(
            inner,
            BertPackedCollate(tokenizer, pack_seq_length, pack_rows,
                              pack_max_per_row, ignore_index=ignore_index,
                              mlm_prob=mlm_prob,
                              emit_loss_mask=emit_loss_mask),
            pack_seq_length, pack_rows, pack_max_per_row,
            pack_horizon=pack_horizon)
    fixed = fixed_seq_lengths
    if isinstance(fixed, (list, tuple)):
        if len(fixed) != 1:
            raise ValueError("unbinned data takes a single fixed_seq_length")
        fixed = fixed[0]
    return DataLoader(
        make_dataset(file_paths),
        batch_size,
        collate_fn=make_collate(fixed),
        prefetch=prefetch,
        worker_mode=worker_mode,
    )
