"""Pallas fused attention: parity with the dense reference (interpret
mode on CPU). tests/test_tpu_compile.py compiles the real kernels for a
v5e; chip_smoke.py runs them on one against dense."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lddl_tpu.ops.flash_attention import flash_attention
from lddl_tpu.ops.ring_attention import dense_attention_reference


def _inputs(b=2, l=200, h=4, d=64, dtype=jnp.float32, seed=0):
    g = np.random.default_rng(seed)
    q = jnp.asarray(g.standard_normal((b, l, h, d)), dtype)
    k = jnp.asarray(g.standard_normal((b, l, h, d)), dtype)
    v = jnp.asarray(g.standard_normal((b, l, h, d)), dtype)
    mask = np.ones((b, l), np.int32)
    mask[0, 128:] = 0   # KV block [128, 256) fully masked (post-pad)
    mask[1, l - 3:] = 0
    return q, k, v, jnp.asarray(mask)


def test_forward_matches_dense():
    q, k, v, mask = _inputs()          # L=200: exercises the padding path
    out = flash_attention(q, k, v, mask)
    ref = dense_attention_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_forward_bf16():
    q, k, v, mask = _inputs(l=128, dtype=jnp.bfloat16)
    out = np.asarray(flash_attention(q, k, v, mask), np.float32)
    ref = np.asarray(dense_attention_reference(q, k, v, mask), np.float32)
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-2)


def test_gradients_match_dense():
    # L=200: the backward's padding path (padded ct/out, masked padded
    # keys, dropped padded query rows) is live.
    q, k, v, mask = _inputs(l=200, seed=3)

    def loss_f(q, k, v):
        return (flash_attention(q, k, v, mask) ** 2).sum()

    def loss_d(q, k, v):
        return (dense_attention_reference(q, k, v, mask) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_jit_composes():
    q, k, v, mask = _inputs(l=128)
    out = jax.jit(flash_attention)(q, k, v, mask)
    ref = dense_attention_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_onekv_dispatch_boundary():
    """L_pad <= 896 runs the single-block kernels (nbh=1 above 512),
    above runs online."""
    from lddl_tpu.ops.flash_attention import _use_onekv, _nbh_for

    assert _use_onekv(512, 64)       # the reference's headline config
    assert _use_onekv(128, 64)
    assert _use_onekv(640, 64) and _use_onekv(896, 64)   # the former band
    assert not _use_onekv(1024, 64)  # online regime
    # the 640-896 extension is compile-validated at head_dim 64 only:
    # wider heads keep the conservative 512 bound (VMEM)
    assert _use_onekv(512, 128) and not _use_onekv(640, 128)
    assert not _use_onekv(512, 256)  # d > 128 is always online
    assert _nbh_for(16, 512) == 4 and _nbh_for(12, 512) == 4  # bert heads
    assert _nbh_for(6, 512) == 2 and _nbh_for(7, 512) == 1
    # single-row cells above 512 (VMEM: [L,L] fp32 temporaries)
    assert _nbh_for(16, 640) == 1 and _nbh_for(12, 896) == 1


def test_onekv_band_matches_dense():
    """L=600 (l_pad=640): the nbh=1 single-block cells that took over the
    former 512 < l_pad < 1024 dense band — forward and gradients vs the
    dense reference."""
    q, k, v, _ = _inputs(l=600, seed=5)
    mask = np.ones((2, 600), np.int32)
    mask[0, 550:] = 0
    mask = jnp.asarray(mask)

    out = flash_attention(q, k, v, mask)
    ref = dense_attention_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_f(q, k, v):
        return (flash_attention(q, k, v, mask) ** 2).sum()

    def loss_d(q, k, v):
        return (dense_attention_reference(q, k, v, mask) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_online_nondividing_blocks_match_dense():
    """l_pad=640 with head_dim=256 (d > 128 fails the single-block gate,
    so this is the reachable online path in the 512-896 range): exercises
    _block_sizes' power-of-two halving fallback (640 % 256 != 0 ->
    tq=tk=128), forward and gradients vs the dense reference."""
    q, k, v, _ = _inputs(l=600, h=2, d=256, seed=13)
    mask = np.ones((2, 600), np.int32)
    mask[0, 550:] = 0
    mask = jnp.asarray(mask)

    out = flash_attention(q, k, v, mask)
    ref = dense_attention_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)

    def loss_f(q, k, v):
        return (flash_attention(q, k, v, mask) ** 2).sum()

    def loss_d(q, k, v):
        return (dense_attention_reference(q, k, v, mask) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_online_path_matches_dense_above_896():
    """L=1000 (l_pad=1024 > ONEKV_MAX_L_PAD): the online-softmax kernels,
    forward AND gradients vs the dense reference."""
    q, k, v, _ = _inputs(l=1000, seed=5)
    mask = np.ones((2, 1000), np.int32)
    mask[0, 900:] = 0
    mask = jnp.asarray(mask)

    out = flash_attention(q, k, v, mask)
    ref = dense_attention_reference(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_f(q, k, v):
        return (flash_attention(q, k, v, mask) ** 2).sum()

    def loss_d(q, k, v):
        return (dense_attention_reference(q, k, v, mask) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("h", [4, 6, 3])
def test_onekv_segments_match_dense_everywhere(h):
    """Packed rows through the single-block kernels: block-diagonal
    attention matches dense at EVERY row, including degenerate
    (segment-id 0 = padding) rows, which softmax the all(-1e9) row to
    the uniform value average under the shared bias convention.
    h=4 runs nbh=4 cells, h=6 nbh=2 cells (three cells per batch row, so
    the mask block index g*nbh//h diverges from the row block index),
    h=3 the nbh=1 cells."""
    g = np.random.default_rng(7)
    b, l, d = 2, 256, 64
    q = jnp.asarray(g.standard_normal((b, l, h, d)), jnp.float32)
    k = jnp.asarray(g.standard_normal((b, l, h, d)), jnp.float32)
    v = jnp.asarray(g.standard_normal((b, l, h, d)), jnp.float32)
    segs_np = np.zeros((b, l), np.int32)
    segs_np[0, :100] = 1
    segs_np[0, 100:200] = 2
    segs_np[1, :250] = 1
    segs = jnp.asarray(segs_np)

    def dense_packed(q, k, v, segs):
        scale = 1.0 / np.sqrt(q.shape[-1])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        allowed = ((segs[:, None, :, None] == segs[:, None, None, :])
                   & (segs[:, None, None, :] > 0))
        probs = jax.nn.softmax(
            scores + jnp.where(allowed, 0.0, -1e9), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = np.asarray(flash_attention(q, k, v, segments=segs))
    ref = np.asarray(dense_packed(q, k, v, segs))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_masked_outlier_key_cannot_underflow_live_rows():
    """Round-5 review regression: a DISALLOWED key whose raw score
    dwarfs every allowed score (gap >> 88, the fp32 exp range) must not
    drag the softmax row max up and underflow the allowed probabilities.
    The -1e9 additive bias keeps the max on the allowed side; a
    multiply-after-exp scheme (max over raw scores) returns 0 here."""
    g = np.random.default_rng(11)
    b, l, h, d = 1, 128, 4, 64
    q = jnp.asarray(g.standard_normal((b, l, h, d)), jnp.float32)
    k_np = g.standard_normal((b, l, h, d))
    k_np[0, 70] = 100.0 * np.asarray(q[0, 0])   # raw score ~ 800 vs ~ O(1)
    k = jnp.asarray(k_np, jnp.float32)
    v = jnp.asarray(g.standard_normal((b, l, h, d)), jnp.float32)
    segs = np.ones((b, l), np.int32)
    segs[0, 70] = 2                              # the outlier is DISALLOWED
    segs[0, 100:] = 0                            # for q rows in segment 1
    segs = jnp.asarray(segs)

    out = flash_attention(q, k, v, segments=segs)
    assert float(jnp.abs(out[0, 0]).max()) > 1e-3   # row did not collapse

    def loss_f(q, k, v):
        return (flash_attention(q, k, v, segments=segs) ** 2).sum()

    dq = jax.grad(loss_f)(q, k, v)
    assert np.isfinite(np.asarray(dq)).all()
    assert float(jnp.abs(dq[0, 0]).max()) > 1e-6


def test_bert_flash_matches_dense_logits():
    """attention_impl='flash' in the full model (interpret mode off-TPU)
    matches dense logits with shared params."""
    import flax.linen as nn
    from lddl_tpu.models import BertConfig, BertForPreTraining
    from lddl_tpu.models.testing import fake_pretrain_batch

    cfg_kw = dict(vocab_size=128, hidden_size=32, num_layers=2, num_heads=4,
                  intermediate_size=64, max_position_embeddings=128,
                  dtype=jnp.float32)
    cfg_d = BertConfig(attention_impl="dense", **cfg_kw)
    cfg_f = BertConfig(attention_impl="flash", **cfg_kw)
    batch = fake_pretrain_batch(cfg_d.vocab_size, 2, 128, seed=1)
    model_d = BertForPreTraining(cfg_d)
    model_f = BertForPreTraining(cfg_f)
    params = nn.meta.unbox(model_d.init(
        jax.random.PRNGKey(0), batch["input_ids"],
        batch["token_type_ids"], batch["attention_mask"],
        deterministic=True))["params"]

    def fwd(model):
        return model.apply({"params": params}, batch["input_ids"],
                           batch["token_type_ids"], batch["attention_mask"],
                           deterministic=True)

    mlm_d, nsp_d = fwd(model_d)
    mlm_f, nsp_f = fwd(model_f)
    np.testing.assert_allclose(np.asarray(mlm_f), np.asarray(mlm_d),
                               rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(nsp_f), np.asarray(nsp_d),
                               rtol=5e-4, atol=5e-4)


# --- attention-probability dropout inside the single-block kernels ------

def _unpacked_keep(words, l):
    """The forward's stored keep-mask [B*H, S, L_pad] (bit j of word
    [q', k] is query row j*S + q') as bool [B*H, L, L]."""
    w = np.asarray(words).view(np.uint32)
    s, l_pad = w.shape[1:]
    rows = [(w >> j) & 1 for j in range(l_pad // s)]
    return np.concatenate(rows, axis=1).astype(bool)[:, :l, :l]


def _hashed_extent(mask, l, s):
    """Bool [B, L, L]: the query rows and keys the forward hashes for each
    batch row, one past its last valid key n: query slabs of S rows below
    ceil(n / S), key blocks of 128 below ceil(n / 128)."""
    mask = np.asarray(mask)
    n = np.array([np.flatnonzero(row).max() + 1 if row.any() else 0
                  for row in mask])
    rows = -(-n // s) * s
    cols = -(-n // 128) * 128
    idx = np.arange(l)
    return ((idx[None, :, None] < rows[:, None, None])
            & (idx[None, None, :] < cols[:, None, None]))


def _dropout_fwd(l, h, rows, seed=21):
    """(q, k, v, mask, key, (out, lse, packed keep words)) of the kernel
    forward with dropout 0.1: ``_inputs``' masks, every token valid, or
    batch row 1 all padding."""
    from lddl_tpu.ops.flash_attention import (flash_attention_fwd,
                                              threefry_key_words)
    q, k, v, mask = _inputs(b=2, l=l, h=h, dtype=jnp.bfloat16, seed=seed)
    if rows == "all-valid":
        mask = jnp.ones_like(mask)
    elif rows == "empty-row":
        mask = mask.at[1].set(0)
    key = jax.random.fold_in(jax.random.PRNGKey(9), 3)
    res = flash_attention_fwd(q, k, v, mask, dropout_rate=0.1,
                              dropout_key=threefry_key_words(key))
    return q, k, v, mask, key, res


@pytest.mark.parametrize("l,h,rows", [
    (256, 4, "inputs"), (200, 4, "inputs"), (256, 2, "inputs"),
    (256, 4, "all-valid"), (256, 4, "empty-row"), (512, 16, "inputs")],
    ids=["L256-nbh4", "L200-nbh4", "L256-nbh2", "all-valid", "empty-row",
         "L512-nbh4"])
def test_dropout_mask_is_bernoulli_bit_for_bit(l, h, rows):
    """Within each batch row's hashed extent the kernel draws exactly
    jax.random.bernoulli's [B, H, L, L] mask for the same threefry key,
    and stores zeros outside it: aligned and unaligned L, cells of 4 and 2
    (batch, head) rows, whose global row index sets each element's
    counter. ``_inputs``' row 0 is valid to 128 of L, so its key blocks
    and query slabs beyond are skipped; with every token valid the stored
    mask is the whole bernoulli tensor; an all-padding row stores none."""
    b = 2
    _, _, _, mask, key, (_, _, words) = _dropout_fwd(l, h, rows)
    keep = _unpacked_keep(words, l).reshape(b, h, l, l)
    want = np.asarray(jax.random.bernoulli(key, 0.9, (b, h, l, l)))
    inside = _hashed_extent(mask, l, words.shape[1])[:, None]
    np.testing.assert_array_equal(keep, want & inside)
    if rows == "all-valid":
        np.testing.assert_array_equal(keep, want)
    if rows == "empty-row":
        assert not np.asarray(words).reshape(b, h, -1)[1].any()


def _pack_keep(keep, l_pad, s):
    """bool [B*H, L, L] -> the kernel's words [B*H, S, L_pad]: bit j of
    word [q', k] is query row j*S + q'."""
    n, l, _ = keep.shape
    full = np.zeros((n, l_pad, l_pad), np.uint32)
    full[:, :l, :l] = keep
    words = np.zeros((n, s, l_pad), np.uint32)
    for j in range(l_pad // s):
        words |= full[:, j * s:(j + 1) * s] << np.uint32(j)
    return jnp.asarray(words.view(np.int32))


@pytest.mark.parametrize("rows", ["inputs", "empty-row"])
def test_skipped_mask_backward_matches_full_mask(rows):
    """The backward reading the forward's words, hashed within each row's
    extent, gives dQ, dK, dV bit for bit those of the whole bernoulli mask
    packed the same way, when padding rows carry a zero cotangent (as in
    training): keys beyond the extent have an exact 0 probability, and
    padding queries add nothing."""
    from lddl_tpu.ops.flash_attention import flash_attention_bwd
    b, l, h = 2, 256, 4
    q, k, v, mask, key, (out, lse, words) = _dropout_fwd(l, h, rows)
    full = _pack_keep(np.asarray(jax.random.bernoulli(
        key, 0.9, (b, h, l, l))).reshape(b * h, l, l), l, words.shape[1])
    g = np.random.default_rng(5)
    ct = jnp.asarray(g.standard_normal(q.shape) * np.asarray(
        mask)[:, :, None, None], jnp.bfloat16)
    skipped = flash_attention_bwd(q, k, v, mask, out, lse, ct,
                                  dropout_rate=0.1, keep=words)
    whole = flash_attention_bwd(q, k, v, mask, out, lse, ct,
                                dropout_rate=0.1, keep=full)
    for a, w in zip(skipped, whole):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("l,rows", [(256, "inputs"), (200, "inputs"),
                                    (256, "empty-row"), (512, "inputs")])
def test_dropout_mask_tiles_match_kernel(l, rows):
    """``dropout_mask_tiles`` applies the kernel's extent rule: per batch
    row, the tiles (S query rows by 128 keys) holding a kept bit of the
    stored words are the tiles it counts as hashed, and the rest are the
    ones it counts as skipped."""
    from lddl_tpu.ops.flash_attention import dropout_mask_tiles
    b, h = 2, 4
    _, _, _, mask, _, (_, _, words) = _dropout_fwd(l, h, rows)
    w = np.asarray(words).reshape(b, h, *words.shape[1:])
    s, l_pad = w.shape[2:]
    pack = l_pad // s
    for i in range(b):
        # [H, pack, S, L_pad] bits -> any set per (slab j, key block c).
        bits = (w[i][:, None] >> np.arange(pack)[None, :, None, None]) & 1
        tiles = bits.reshape(h, pack, s, l_pad // 128, 128).any(
            axis=(2, 4))
        hashed, skipped = dropout_mask_tiles(np.asarray(mask)[i:i + 1])
        assert hashed + skipped == pack * (l_pad // 128)
        for head in tiles:
            assert int(head.sum()) == hashed, (i, hashed)
            # The hashed tiles are the leading slabs by leading blocks.
            rows_, cols = np.nonzero(head)
            if hashed:
                assert head[:rows_.max() + 1, :cols.max() + 1].all()
    per_row = [dropout_mask_tiles(np.asarray(mask)[i:i + 1])
               for i in range(b)]
    assert dropout_mask_tiles(mask) == tuple(np.sum(per_row, axis=0))


def _dense_dropout_attention(q, k, v, allowed, key, rate):
    """The dense path's math in fp32: softmax, then the models' stored-mask
    Dropout with ``key``, then the context."""
    from lddl_tpu.models.attention import Dropout
    q, k, v = (t.astype(jnp.float32) for t in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(scores + jnp.where(allowed, 0.0, -1e9), axis=-1)
    probs = Dropout(rate).apply({}, probs, deterministic=False, rng=key)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


@pytest.mark.parametrize("packed", [False, True],
                         ids=["padding", "segments"])
def test_dropout_matches_dense_dropout(packed):
    """Forward and dQ/dK/dV of the kernels with dropout against the dense
    path's Dropout-masked attention under the same key, at bf16 tolerance:
    a padding mask, and packed rows (the mask does not depend on
    segments). The output and the loss read the real tokens' rows, as
    training's does: the kernel hashes no mask for query rows past a
    row's last valid key, so their output is not dense's; and a packed
    row's padding queries (segment 0) attend to no key, and the
    single-block backward's lse (~ -1e9) cannot hold their log-sum, so
    their gradient is not dense's. Their cotangent is zero in training."""
    b, l, h = 2, 256, 4
    q, k, v, mask = _inputs(b=b, l=l, h=h, dtype=jnp.bfloat16, seed=23)
    key = jax.random.PRNGKey(31)
    if packed:
        segs = np.zeros((b, l), np.int32)
        segs[0, :100], segs[0, 100:230] = 1, 2
        segs[1, :250] = 1
        segs = jnp.asarray(segs)
        allowed = ((segs[:, None, :, None] == segs[:, None, None, :])
                   & (segs[:, None, None, :] > 0))
        real = (segs > 0)[:, :, None, None]
        kw = dict(segments=segs)
    else:
        allowed = mask[:, None, None, :] > 0
        real = (mask > 0)[:, :, None, None]
        kw = dict(kv_mask=mask)

    def loss_f(q, k, v):
        out = flash_attention(q, k, v, dropout_rate=0.1, dropout_rng=key,
                              **kw)
        return (jnp.where(real, out.astype(jnp.float32), 0.0) ** 2).sum(), out

    def loss_d(q, k, v):
        out = _dense_dropout_attention(q, k, v, allowed, key, 0.1)
        return (jnp.where(real, out, 0.0) ** 2).sum(), out

    (_, out), gf = jax.value_and_grad(loss_f, (0, 1, 2), has_aux=True)(
        q, k, v)
    (_, ref), gd = jax.value_and_grad(loss_d, (0, 1, 2), has_aux=True)(
        q, k, v)
    real = np.broadcast_to(real, out.shape)
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(ref)[real], rtol=2e-2, atol=2e-2)
    for a, r in zip(gf, gd):
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(a, np.float32), r, rtol=2e-2,
                                   atol=2e-2 * np.abs(r).max())


def test_dropout_needs_single_block_kernels_and_threefry():
    """Kernel dropout is refused where the online kernels serve (they
    draw no mask) and for a key whose mask it cannot replay."""
    q, k, v, mask = _inputs(l=1000, h=1, seed=2)
    with pytest.raises(ValueError, match="single-block"):
        flash_attention(q, k, v, mask, dropout_rate=0.1,
                        dropout_rng=jax.random.PRNGKey(0))
    q, k, v, mask = _inputs(l=256, h=1, seed=2)
    with pytest.raises(ValueError, match="threefry"):
        flash_attention(q, k, v, mask, dropout_rate=0.1,
                        dropout_rng=jax.random.key(0, impl="rbg"))
