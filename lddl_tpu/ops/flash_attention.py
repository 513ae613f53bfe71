"""Pallas fused attention for TPU — two regimes behind one entry point.

Short sequences (L_pad <= 896 at the standard head_dim 64; the
reference's headline pretraining regime L=512 —
/root/reference/lddl/dask/bert/pretrain.py:627-637 — sits here): the
"single-block" kernels. The whole L x L score matrix for one (batch,
head) row fits VMEM, so the forward computes an ordinary (not online)
softmax in one pass, and the backward is ONE fused kernel that
recomputes P once and emits dQ, dK, dV together (5 matmuls vs the
two-kernel online recipe's 7). Cells are fat: ``nbh`` (batch, head)
rows per grid cell (same batch row, so the mask/allowed matrix is built
once per cell; one row above L_pad 512, where the temporaries grow),
which amortizes per-cell overheads that dominate at short L — this is
what makes the pallas kernel BEAT XLA's fused dense attention from
L_pad 256 through 896 (round-5 builder probe, not measured by the driver), where rounds 3-4 lost to it at 512 and
below.

The single-block kernels also drop attention probabilities: the forward
hashes the keep-mask that ``jax.random.bernoulli`` would draw from the
same threefry key, bit for bit within each row's valid extent, and
stores it bit-packed for the backward (see "Attention-probability
dropout" below). The online kernels have no dropout.

Long sequences: the flash-style online-softmax kernels. Per (batch,
head), Q blocks stream through VMEM while the kernel walks K/V blocks
under a running-max/denominator softmax — the L x L score matrix never
exists anywhere. Backward recomputes P blockwise from (Q, K, LSE): dQ
in a Q-block kernel, dK/dV in a KV-block kernel.

Both regimes: matmul operands stay in the stored dtype (bf16 in
training) with fp32 accumulation on the MXU
(``preferred_element_type``); the forward saves per-row log-sum-exp for
the backward; numerics match ops.ring_attention.dense_attention_reference
pinned by interpret-mode tests on CPU for forward AND gradients, and
the same entry point compiles for a v5e (tests/test_tpu_compile.py) and
runs on one (chip_smoke.py phase 4 checks it against dense).

``interpret=None`` auto-selects: interpret mode on the CPU backend (CI),
the real pallas lowering on every other backend.
"""

import collections
import functools
import math

# jax imported inside functions: the offline pipeline stages must stay
# importable (via lddl_tpu.ops) on machines where jax is absent/broken.

def _block_sizes(l_pad):
    """(TQ, TK) tuned on a real v5e chip (round-3 sweep, fwd+bwd wall):
    128x128 blocks leave 2.5-3.6x on the table vs the MXU-filling sizes
    below — the inner dots must be big enough to amortize per-step
    overhead. l_pad is a multiple of 128, so the fallbacks always divide."""
    tq = 256 if l_pad <= 1024 else 512
    while l_pad % tq:
        tq //= 2
    tk = 512
    while l_pad % tk:
        tk //= 2
    return min(tq, l_pad), min(tk, l_pad)


def _interpret_default():
    """Interpret mode on the CPU backend only (tests). Any other backend
    lowers the real kernel, and fails loudly where it cannot: a silent
    interpreter fallback on an accelerator would pass every check while
    running the slow path."""
    import jax
    return jax.default_backend() == "cpu"


def _dot(a, b, transpose_b=False):
    import jax
    import jax.numpy as jnp
    dims = (((1,), (1,)), ((), ())) if transpose_b else (((1,), (0,)),
                                                         ((), ()))
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, qmask_ref, o_ref, lse_ref,
                *, scale, n_kv, tk):
    import jax.numpy as jnp
    import jax.lax as lax
    from jax.experimental import pallas as pl

    # Matmul OPERANDS stay in the stored dtype (bf16 in training): the MXU
    # runs bf16 x bf16 -> fp32 at full rate but fp32 x fp32 at ~1/4 rate —
    # casting inputs up was measured to cost the whole kernel its lead
    # (round-3 builder probe, not measured by the driver). Softmax
    # statistics stay fp32.
    q = q_ref[0]                                # [TQ, D], stored dtype
    qm = qmask_ref[0, 0]                        # [TQ] segment ids
    tq, d = q.shape

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * tk, tk), :]
        v_blk = v_ref[0, pl.ds(j * tk, tk), :]
        msk = mask_ref[0, 0, pl.ds(j * tk, tk)]
        s = _dot(q, k_blk, transpose_b=True) * scale      # fp32 [TQ, TK]
        # Attend iff the key is valid AND in the query's segment (plain
        # padding masks are the one-segment special case: q side all 1s).
        allowed = (msk[None, :] > 0) & (msk[None, :] == qm[:, None])
        s = s + jnp.where(allowed, 0.0, -1e9)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                            # fp32 [TQ, TK]
        corr = jnp.exp(m - m_new)                         # [TQ, 1]
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc * corr + _dot(p.astype(v_blk.dtype), v_blk)
        return m_new, l_new, acc_new

    m0 = jnp.full((tq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((tq, 1), jnp.float32)
    acc0 = jnp.zeros((tq, d), jnp.float32)
    m, l, acc = lax.fori_loop(0, n_kv, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = (m[:, 0] + jnp.log(l[:, 0])).astype(lse_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, qmask_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, *, scale, n_kv, tk):
    import jax.numpy as jnp
    import jax.lax as lax
    from jax.experimental import pallas as pl

    q = q_ref[0]                                 # [TQ, D], stored dtype
    qm = qmask_ref[0, 0]                         # [TQ] segment ids
    do = do_ref[0]                               # [TQ, D]
    lse = lse_ref[0, 0][:, None]                 # [TQ, 1]
    delta = delta_ref[0, 0][:, None]             # [TQ, 1]
    tq, d = q.shape

    def body(j, dq_acc):
        k_blk = k_ref[0, pl.ds(j * tk, tk), :]
        v_blk = v_ref[0, pl.ds(j * tk, tk), :]
        msk = mask_ref[0, 0, pl.ds(j * tk, tk)]
        s = _dot(q, k_blk, transpose_b=True) * scale
        allowed = (msk[None, :] > 0) & (msk[None, :] == qm[:, None])
        s = s + jnp.where(allowed, 0.0, -1e9)
        p = jnp.exp(s - lse)                     # fp32 [TQ, TK]
        dp = _dot(do, v_blk, transpose_b=True)   # fp32 [TQ, TK]
        ds = (p * (dp - delta) * scale).astype(k_blk.dtype)
        return dq_acc + _dot(ds, k_blk)

    dq = lax.fori_loop(0, n_kv, body, jnp.zeros((tq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, qmask_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, *, scale, n_q, tq):
    import jax.numpy as jnp
    import jax.lax as lax
    from jax.experimental import pallas as pl

    k = k_ref[0]                                 # [TK, D], stored dtype
    v = v_ref[0]                                 # [TK, D]
    msk = mask_ref[0, 0]                         # [TK] (this KV block)
    tk, d = k.shape

    def body(i, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[0, pl.ds(i * tq, tq), :]
        do_blk = do_ref[0, pl.ds(i * tq, tq), :]
        qm = qmask_ref[0, 0, pl.ds(i * tq, tq)]            # [TQ]
        lse = lse_ref[0, 0, pl.ds(i * tq, tq)][None, :]    # [1, TQ]
        delta = delta_ref[0, 0, pl.ds(i * tq, tq)][None, :]
        allowed = (msk[:, None] > 0) & (msk[:, None] == qm[None, :])
        bias = jnp.where(allowed, 0.0, -1e9)               # [TK, TQ]
        # s^T layout: [TK, TQ]
        st = _dot(k, q_blk, transpose_b=True) * scale + bias
        pt = jnp.exp(st - lse)                   # fp32 [TK, TQ]
        dv_acc = dv_acc + _dot(pt.astype(do_blk.dtype), do_blk)  # [TK, D]
        dpt = _dot(v, do_blk, transpose_b=True)  # fp32 [TK, TQ]
        dst = (pt * (dpt - delta) * scale).astype(q_blk.dtype)
        dk_acc = dk_acc + _dot(dst, q_blk)       # [TK, D]
        return dk_acc, dv_acc

    zero = jnp.zeros((tk, d), jnp.float32)
    dk, dv = lax.fori_loop(0, n_q, body, (zero, zero))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _prep_one(t, l_pad):
    """Pad one [B, L, H, D] tensor to l_pad rows and move it to the
    [B*H, L, D] kernel layout."""
    import jax.numpy as jnp
    b, l, h, d = t.shape
    if l_pad != l:
        t = jnp.pad(t, ((0, 0), (0, l_pad - l), (0, 0), (0, 0)))
    return t.transpose(0, 2, 1, 3).reshape(b * h, l_pad, d)


def _prep_mask(m, l_pad):
    import jax.numpy as jnp
    b, l = m.shape
    if l_pad != l:
        m = jnp.pad(m, ((0, 0), (0, l_pad - l)))
    return m.astype(jnp.int32).reshape(b, 1, l_pad)


def _prep(q, k, v, kv_mask, q_mask):
    """Pad L to a block multiple and move to the [B*H, L, D] kernel
    layout. Masks may be binary validity or per-token segment ids (packed
    rows); q_mask defaults to all-ones = "every query in segment 1".
    Returns (qb, kb, vb, maskb[B,1,Lp], qmaskb[B,1,Lp], shapes)."""
    import jax.numpy as jnp
    b, l, h, d = q.shape
    l_pad = pad_seq_len(l)
    if q_mask is None:
        # Plain padding mask: the kernel's test is (msk > 0) & (msk == qm),
        # so a truthy value other than 1 (int mask from a sum, bool*2, ...)
        # must normalize to 1 or it would mask EVERYTHING against the
        # all-ones q side (ADVICE round 3). NOTE: segment ids passed as
        # kv_mask WITHOUT the matching q_mask also collapse to all-1s here
        # — packed callers must pass the segment array as BOTH masks (as
        # models/bert.py does); values are invisible at trace time, so
        # this cannot be asserted.
        kv_mask = (kv_mask != 0).astype(jnp.int32)
        q_mask = jnp.ones((b, l), jnp.int32)
    return (_prep_one(q, l_pad), _prep_one(k, l_pad), _prep_one(v, l_pad),
            _prep_mask(kv_mask, l_pad), _prep_mask(q_mask, l_pad),
            (b, l, h, d, l_pad))


def _from_bh(t, b, l, h, d):
    return t.reshape(b, h, -1, d).transpose(0, 2, 1, 3)[:, :l]


def flash_attention_fwd(q, k, v, kv_mask, interpret=None, q_mask=None,
                        dropout_rate=0.0, dropout_key=None):
    """Fused attention forward: q/k/v [B, L, H, D], kv_mask [B, L]
    (1 = attend, or per-token segment ids for packed rows — pass the same
    array as q_mask and attention becomes block-diagonal within rows).
    Returns (out [B, L, H, D], lse [B*H, 1, L_pad], keep); fp32
    accumulation, output in q.dtype. L pads to a 128 multiple internally
    (padded keys are masked; padded query rows are dropped on return).

    ``dropout_rate`` > 0 drops attention probabilities inside the
    single-block kernels with the keep-mask ``jax.random.bernoulli(
    dropout_key, 1 - dropout_rate, (B, H, L, L))`` draws, bit for bit
    within each row's valid extent (``dropout_key``: the int32 words of a
    threefry key, see ``threefry_key_words``); ``keep`` is that mask,
    bit-packed, for the backward (None without dropout). A row's extent
    ends at its last valid key: query rows past it are padding, whose
    output is not defined under dropout and whose cotangent must be 0,
    as every loss that ignores padding gives."""
    import jax
    from jax.experimental import pallas as pl
    import jax.numpy as jnp

    if interpret is None:
        interpret = _interpret_default()
    qb, kb, vb, maskb, qmaskb, (b, l, h, d, l_pad) = _prep(
        q, k, v, kv_mask, q_mask)
    scale = 1.0 / (d ** 0.5)
    drop = _kernel_dropout(dropout_rate, b, l, h, d)
    if _use_onekv(l_pad, d):
        args = [qb, kb, vb, maskb, qmaskb]
        if drop is not None:
            args += [dropout_key, _row_extent(maskb[:, 0], jnp)]
        res = _onekv_call(False, b, h, l_pad, d, (q.dtype,) * 3, drop,
                          interpret)(*args)
        keep = res[2] if drop is not None else None
        return _from_bh(res[0], b, l, h, d), res[1], keep
    tq, tk = _block_sizes(l_pad)
    assert l_pad % tq == 0 and l_pad % tk == 0, (l_pad, tq, tk)
    kernel = functools.partial(_fwd_kernel, scale=scale,
                               n_kv=l_pad // tk, tk=tk)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=(b * h, l_pad // tq),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, l_pad, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, l_pad, d), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((1, 1, l_pad),
                         lambda bh, qi: (bh // h, 0, 0)),
            pl.BlockSpec((1, 1, tq), lambda bh, qi: (bh // h, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, tq), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, l_pad, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, l_pad), jnp.float32),
        ],
        interpret=interpret,
    )(qb, kb, vb, maskb, qmaskb)
    return _from_bh(out, b, l, h, d), lse, None


def flash_attention_bwd(q, k, v, kv_mask, out, lse, ct, interpret=None,
                        q_mask=None, dropout_rate=0.0, keep=None):
    """Pallas backward. Single-block regime: ONE fused kernel recomputes P
    once and emits dQ, dK, dV together; with dropout it reads the
    forward's stored ``keep`` mask (no second hash). Online regime: P
    recomputed blockwise from (Q, K, LSE); dQ from a Q-block kernel,
    dK/dV from a KV-block kernel."""
    import jax
    from jax.experimental import pallas as pl
    import jax.numpy as jnp

    if interpret is None:
        interpret = _interpret_default()
    qb, kb, vb, maskb, qmaskb, (b, l, h, d, l_pad) = _prep(
        q, k, v, kv_mask, q_mask)
    dob = _prep_one(ct, l_pad)
    ob = _prep_one(out, l_pad)
    scale = 1.0 / (d ** 0.5)
    # delta_i = sum_d dO_id * O_id, per query row.
    delta = (dob.astype(jnp.float32) * ob.astype(jnp.float32)).sum(
        axis=-1).reshape(b * h, 1, l_pad)

    if _use_onekv(l_pad, d):
        args = [qb, kb, vb, maskb, qmaskb, dob, lse, delta]
        drop = _kernel_dropout(dropout_rate, b, l, h, d)
        if drop is not None:
            args.append(keep)
        dq, dk, dv = _onekv_call(True, b, h, l_pad, d,
                                 (q.dtype, k.dtype, v.dtype), drop,
                                 interpret)(*args)
        return (_from_bh(dq, b, l, h, d), _from_bh(dk, b, l, h, d),
                _from_bh(dv, b, l, h, d))

    tq, tk = _block_sizes(l_pad)
    assert l_pad % tq == 0 and l_pad % tk == 0, (l_pad, tq, tk)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale,
                          n_kv=l_pad // tk, tk=tk),
        name="flash_bwd_dq",
        grid=(b * h, l_pad // tq),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda bh, qi: (bh, qi, 0)),   # q
            pl.BlockSpec((1, l_pad, d), lambda bh, qi: (bh, 0, 0)),  # k
            pl.BlockSpec((1, l_pad, d), lambda bh, qi: (bh, 0, 0)),  # v
            pl.BlockSpec((1, 1, l_pad),
                         lambda bh, qi: (bh // h, 0, 0)),            # mask
            pl.BlockSpec((1, 1, tq),
                         lambda bh, qi: (bh // h, 0, qi)),           # qmask
            pl.BlockSpec((1, tq, d), lambda bh, qi: (bh, qi, 0)),   # do
            pl.BlockSpec((1, 1, tq), lambda bh, qi: (bh, 0, qi)),   # lse
            pl.BlockSpec((1, 1, tq), lambda bh, qi: (bh, 0, qi)),   # delta
        ],
        out_specs=pl.BlockSpec((1, tq, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, l_pad, d), q.dtype),
        interpret=interpret,
    )(qb, kb, vb, maskb, qmaskb, dob, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale,
                          n_q=l_pad // tq, tq=tq),
        name="flash_bwd_dkdv",
        grid=(b * h, l_pad // tk),
        in_specs=[
            pl.BlockSpec((1, l_pad, d), lambda bh, ki: (bh, 0, 0)),  # q
            pl.BlockSpec((1, tk, d), lambda bh, ki: (bh, ki, 0)),   # k
            pl.BlockSpec((1, tk, d), lambda bh, ki: (bh, ki, 0)),   # v
            pl.BlockSpec((1, 1, tk),
                         lambda bh, ki: (bh // h, 0, ki)),           # mask
            pl.BlockSpec((1, 1, l_pad),
                         lambda bh, ki: (bh // h, 0, 0)),            # qmask
            pl.BlockSpec((1, l_pad, d), lambda bh, ki: (bh, 0, 0)),  # do
            pl.BlockSpec((1, 1, l_pad), lambda bh, ki: (bh, 0, 0)),  # lse
            pl.BlockSpec((1, 1, l_pad), lambda bh, ki: (bh, 0, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, tk, d), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, tk, d), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, l_pad, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, l_pad, d), v.dtype),
        ],
        interpret=interpret,
    )(qb, kb, vb, maskb, qmaskb, dob, lse, delta)
    return (_from_bh(dq, b, l, h, d), _from_bh(dk, b, l, h, d),
            _from_bh(dv, b, l, h, d))


# ---------------------------------------------------------------------------
# Single-block ("onekv") kernels: the L_pad <= ONEKV_MAX_L_PAD regime.
#
# Per grid cell, ``nbh`` consecutive (batch, head) rows — all of the SAME
# batch row (dispatch guarantees nbh divides num_heads) — are processed with
# whole-row [L, L] score matrices in VMEM. The -1e9 additive mask (the same
# finite-bias convention as the online kernels and the dense reference —
# never multiply-after-exp, whose raw-score row max lets one disallowed
# outlier key underflow every allowed probability) is built ONCE per cell
# and reused by all nbh rows; the 1/l normalization is folded into the
# [L, D] output instead of the [L, L] probabilities. The backward is one
# fused kernel: P is recomputed once and dQ, dK, dV all emitted from it.
# ---------------------------------------------------------------------------


ONEKV_MAX_L_PAD = 896


def pad_seq_len(l):
    """_prep's padding rule: L pads to the next multiple of 128."""
    return -(-l // 128) * 128


def _use_onekv(l_pad, d):
    """Single-block dispatch: the [L, L] per-row score matrix and the fused
    backward's temporaries must fit VMEM alongside nbh rows of blocks
    (nbh drops to 1 above 512 — see _nbh_for; 896 is the largest l_pad
    whose fused-backward temporaries, ~3 fp32 [L, L] + one bf16 [L, L],
    still compile at nbh=1; 1024 does not fit). The extended 640-896
    range is compile-validated at head_dim 64 only (every BERT/BART
    preset) — wider heads double the per-row blocks on top of the ~11 MB
    of [896, 896] temporaries, so they keep the conservative 512 bound."""
    max_l = ONEKV_MAX_L_PAD if d <= 64 else 512
    return l_pad <= max_l and d <= 128


def single_block_serves(seq_len, head_dim):
    """True when flash_attention will dispatch the single-block kernels for
    this shape AND they are in their measured winning range (l_pad >= 256 —
    dense keeps the shortest bins; round-5 builder probe, not measured by the driver). The ONE predicate
    models/attention.resolve_auto_impl consults, so the selector can never
    drift from the dispatcher."""
    l_pad = pad_seq_len(seq_len)
    return l_pad >= 256 and _use_onekv(l_pad, head_dim)


def kernel_dropout_serves(seq_len, head_dim):
    """True when flash_attention drops attention probabilities for this
    shape: the single-block kernels serve it (the online ones skip
    dropout)."""
    return _use_onekv(pad_seq_len(seq_len), head_dim)


def _nbh_for(h, l_pad):
    """Rows per cell: largest of 4/2/1 dividing num_heads so every cell's
    rows share one batch row (mask built once per cell) — but 1 above
    l_pad 512, where a single row's [L, L] fp32 temporaries already take
    ~2.5 MB each and unrolled multi-row cells blow VMEM."""
    if l_pad > 512:
        return 1
    return 4 if h % 4 == 0 else (2 if h % 2 == 0 else 1)


def _dot0(a, b):
    """Contract over axis 0 of both: a [M, N], b [M, D] -> [N, D]."""
    import jax
    import jax.numpy as jnp
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _cell_bias(mask_ref, qmask_ref):
    """[L, L] additive mask for one cell (rows share the batch row):
    0 where the key is valid AND in the query's segment, -1e9 elsewhere —
    the same finite-bias convention as the online kernels (fp32 min would
    overflow in bf16; exp(-1e9 - m) underflows to an exact 0 probability,
    and an all-masked row softmaxes to the uniform average, matching the
    dense reference)."""
    import jax.numpy as jnp
    msk = mask_ref[0, 0]
    qm = qmask_ref[0, 0]
    allowed = (msk[None, :] > 0) & (msk[None, :] == qm[:, None])
    return jnp.where(allowed, 0.0, -1e9)


def _onekv_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, qmask_ref, *refs,
                      scale, nbh, heads, drop):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if drop is None:
        o_ref, lse_ref = refs
    else:
        key_ref, extent_ref, o_ref, lse_ref, keep_ref = refs
        row0 = pl.program_id(0) * nbh
        _store_keep_words(key_ref[0], key_ref[1], row0,
                          extent_ref[row0 // heads], keep_ref, drop)
    bias = _cell_bias(mask_ref, qmask_ref)
    for i in range(nbh):
        q = q_ref[i]                             # [L, D], stored dtype
        k = k_ref[i]
        v = v_ref[i]
        s = _dot(q, k, transpose_b=True) * scale + bias  # fp32 [L, L]
        m = s.max(axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(p.sum(axis=1, keepdims=True), 1e-30)
        inv = 1.0 / l
        if drop is not None:
            # Normalized by the undropped sum (l, lse), then dropped and
            # scaled: select(mask, softmax / keep, 0), as Dropout does.
            p = jnp.where(_unpack_keep(keep_ref[i], drop.pack), p, 0.0)
            inv = inv * (1.0 / drop.keep_prob)
        o = _dot(p.astype(v.dtype), v)            # [L, D] fp32, unnormalized
        o_ref[i] = (o * inv).astype(o_ref.dtype)
        lse_ref[i, 0] = m[:, 0] + jnp.log(l[:, 0])


def _onekv_bwd_kernel(q_ref, k_ref, v_ref, mask_ref, qmask_ref, do_ref,
                      lse_ref, delta_ref, *refs, scale, nbh, drop):
    import jax.numpy as jnp

    if drop is None:
        dq_ref, dk_ref, dv_ref = refs
    else:
        keep_ref, dq_ref, dk_ref, dv_ref = refs
    bias = _cell_bias(mask_ref, qmask_ref)
    for i in range(nbh):
        q = q_ref[i]
        k = k_ref[i]
        v = v_ref[i]
        do = do_ref[i]
        lse = lse_ref[i, 0][:, None]             # [L, 1]
        delta = delta_ref[i, 0][:, None]
        s = _dot(q, k, transpose_b=True) * scale + bias
        p = jnp.exp(s - lse)                     # fp32 [Lq, Lk]
        dp = _dot(do, v, transpose_b=True)       # fp32 [Lq, Lk]
        pv = p                                   # what multiplied V
        if drop is not None:
            # out = (M*P/keep) V: dV = (M*P/keep)^T dO and
            # dP = M*(dO V^T)/keep; dS = P*(dP - delta) holds as is, since
            # delta = rowsum(dO*O) and O already carries the mask.
            keep = _unpack_keep(keep_ref[i], drop.pack)
            inv_keep = 1.0 / drop.keep_prob
            pv = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        dv_ref[i] = _dot0(pv.astype(do.dtype), do).astype(dv_ref.dtype)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_ref[i] = _dot(ds, k).astype(dq_ref.dtype)
        dk_ref[i] = _dot0(ds, q).astype(dk_ref.dtype)


# ---------------------------------------------------------------------------
# Attention-probability dropout inside the single-block kernels.
#
# The forward draws the keep-mask ``jax.random.bernoulli(key, 1 - rate,
# (B, H, L, L))`` itself, bit for bit within each batch row's valid
# extent. With a threefry key (and jax_threefry_partitionable, the
# default) element ((b*H + h)*L + q)*L + k is kept iff ((x0 ^ x1) >> 9) <
# ceil(float32(1 - rate) * 2**23), where (x0, x1) = threefry2x32(key, (0,
# that index)): the 23 bits ``uniform`` turns into a float in [0, 1).
# Rows and columns >= L are don't-care. The mask is stored bit-packed for
# the backward, which never hashes again: word [q', k] of a row holds in
# bit j the element of query row j*S + q' (S = L_pad / pack), so packing
# and unpacking are elementwise shifts of [S, L_pad] slabs.
#
# A row's extent n is one past its last valid key. Keys >= n take an exact
# 0 probability whatever their bit, and queries >= n are padding, which no
# loss reads (their cotangent is 0, so they add nothing to dQ, dK, dV). So
# the forward hashes only the tiles of query slabs j < ceil(n / S) by
# 128-lane key blocks c with c*128 < n, and stores zeros elsewhere.
# ---------------------------------------------------------------------------

_KEY_TILE = 128

_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


class _KernelDropout(collections.namedtuple(
        "_KernelDropout", ("keep_prob", "seq_len", "pack"))):
    """Static dropout parameters of a kernel."""

    __slots__ = ()

    @property
    def threshold(self):
        """bernoulli keeps an element iff its 23 uniform bits, read as an
        integer, are below this."""
        import numpy as np
        return math.ceil(float(np.float32(self.keep_prob)) * 2 ** 23)


def _kernel_dropout(rate, b, l, h, d):
    """The kernels' dropout parameters, or None for rate 0."""
    if rate == 0.0:
        return None
    l_pad = pad_seq_len(l)
    if not 0.0 < rate < 1.0:
        raise ValueError("dropout_rate must be in [0, 1): {}".format(rate))
    if not _use_onekv(l_pad, d):
        raise ValueError(
            "attention-probability dropout runs in the single-block kernels "
            "only (L_pad <= {} at head_dim 64); L={} head_dim={} takes the "
            "online kernels".format(ONEKV_MAX_L_PAD, l, d))
    if b * h * l * l > 2 ** 32:
        raise ValueError(
            "kernel dropout counts mask elements in 32 bits: B*H*L*L = {} "
            "exceeds 2**32".format(b * h * l * l))
    return _KernelDropout(1.0 - rate, l, _pack_for(l_pad))


def _pack_for(l_pad):
    """Query rows a keep-mask word holds: 32 where the slab height S stays
    a multiple of the 8-row sublane tile, else 16 (l_pad is a multiple of
    128)."""
    return 32 if (l_pad // 32) % 8 == 0 else 16


def _row_extent(mask, xp):
    """Each row's valid extent for a [B, L] key mask (> 0 = valid): one
    past its last valid index, 0 for an all-padding row. ``xp`` is numpy
    or jax.numpy, so the host's count and the kernel's bound are one rule.
    The last valid index, not a count: a row with holes still hashes every
    live entry."""
    pos = xp.arange(1, mask.shape[1] + 1, dtype=xp.int32)
    return xp.max(xp.where(mask > 0, pos, 0), axis=1).astype(xp.int32)


def dropout_mask_tiles(attention_mask):
    """(hashed, skipped): the keep-mask tiles, one query slab by one
    128-lane key block of a batch row, that the single-block forward
    hashes and skips for the [B, L] ``attention_mask`` (the extent rule of
    ``_store_keep_words``; each of a row's heads repeats its count)."""
    import numpy as np
    mask = np.asarray(attention_mask)
    b, l = mask.shape
    l_pad = pad_seq_len(l)
    s = l_pad // _pack_for(l_pad)
    n = _row_extent(mask, np).astype(np.int64)
    hashed = int((-(-n // s) * -(-n // _KEY_TILE)).sum())
    return hashed, b * (l_pad // s) * (l_pad // _KEY_TILE) - hashed


def threefry_key_words(key):
    """A threefry2x32 PRNG key (raw uint32[2] or typed) as the int32[2]
    the kernels take, or None for a key of another implementation."""
    import jax
    import jax.numpy as jnp
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        if str(jax.random.key_impl(key)) != "threefry2x32":
            return None
        key = jax.random.key_data(key)
    elif (key.shape != (2,)
          or str(jax.config.jax_default_prng_impl) != "threefry2x32"):
        return None
    return jax.lax.bitcast_convert_type(key, jnp.int32)


def replays_bernoulli(key):
    """True when the kernels draw exactly the mask ``jax.random.bernoulli``
    draws from ``key``: a threefry2x32 key, counted as
    ``jax_threefry_partitionable`` (the default) counts."""
    import jax
    return (threefry_key_words(key) is not None
            and jax.config.jax_threefry_partitionable)


def _threefry2x32(k0, k1, x0, x1):
    """jax.random's threefry2x32 hash in int32 arithmetic: adds wrap and
    right shifts are logical, so every bit equals the uint32 original."""
    import jax.lax as lax

    def rotl(x, r):
        return lax.shift_left(x, r) | lax.shift_right_logical(x, 32 - r)

    ks = (k0, k1, k0 ^ k1 ^ 0x1BD11BDA)
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(5):
        for r in _THREEFRY_ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + (ks[(i + 2) % 3] + (i + 1))     # one add on the vector
    return x0, x1


def _keep_words(k0, k1, row0, col0, n_slabs, shape, drop):
    """Bit-packed keep-mask words [rows, S, 128] of kernel rows row0,
    row0 + 1, ... (= b*H + h) at key columns col0 .. col0 + 127, hashed
    one [rows, S, 128] tile of query slabs (rows S apart) at a time for
    the first ``n_slabs`` slabs; the bits of later slabs stay 0. Stacking
    the cell's rows keeps as many vregs in flight as a whole [S, L_pad]
    slab of one row."""
    import jax.lax as lax
    import jax.numpy as jnp

    l = drop.seq_len
    s = shape[1]
    ri = lax.broadcasted_iota(jnp.int32, shape, 0)
    qi = lax.broadcasted_iota(jnp.int32, shape, 1)
    ki = lax.broadcasted_iota(jnp.int32, shape, 2)
    # Element counters of slab 0 (past 2**31 they wrap as uint32 would).
    base = (row0 + ri) * (l * l) + qi * l + (ki + col0)

    def body(j, words):
        x0, x1 = _threefry2x32(k0, k1, 0, base + j * (s * l))
        keep = lax.shift_right_logical(x0 ^ x1, 9) < drop.threshold
        return jnp.where(keep, words | lax.shift_left(jnp.int32(1), j),
                         words)

    return lax.fori_loop(0, n_slabs, body, jnp.zeros(shape, jnp.int32))


def _store_keep_words(k0, k1, row0, n, keep_ref, drop):
    """Hash the keep-masks of kernel rows row0, row0 + 1, ... into
    ``keep_ref`` [rows, S, L_pad] within the rows' valid extent ``n``
    (they share one batch row): query slabs j < ceil(n / S), a dynamic
    loop bound, by key blocks c*128 < n, each at a static lane offset
    under its own guard. Every word is written: a skipped key block stores
    zeros, so the stored mask is deterministic."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rows, s, l_pad = keep_ref.shape
    shape = (rows, s, _KEY_TILE)
    n_slabs = (n + s - 1) // s
    for col0 in range(0, l_pad, _KEY_TILE):
        lanes = pl.ds(col0, _KEY_TILE)

        @pl.when(col0 < n)
        def _hash():
            keep_ref[:, :, lanes] = _keep_words(k0, k1, row0, col0, n_slabs,
                                                shape, drop)

        @pl.when(col0 >= n)
        def _skip():
            keep_ref[:, :, lanes] = jnp.zeros(shape, jnp.int32)


def _unpack_keep(words, pack):
    """[S, L_pad] packed words -> [pack * S, L_pad] bool keep-mask."""
    import jax.lax as lax
    import jax.numpy as jnp
    s, l_pad = words.shape
    bit = lax.broadcasted_iota(jnp.int32, (pack, s, l_pad), 0)
    words = jnp.broadcast_to(words, (pack, s, l_pad))
    return (lax.shift_right_logical(words, bit) & 1).reshape(
        pack * s, l_pad) != 0


def _dropout_specs(drop, nbh, l_pad, n_rows):
    """(SMEM spec, keep-mask spec, keep-mask shape) for grid (b*h // nbh,):
    the SMEM spec takes the key words and the rows' valid extents whole."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s = l_pad // drop.pack
    return (pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((nbh, s, l_pad), lambda g: (g, 0, 0)),
            jax.ShapeDtypeStruct((n_rows, s, l_pad), jnp.int32))


@functools.lru_cache(maxsize=64)
def _onekv_call(backward, b, h, l_pad, d, dtypes, drop, interpret):
    """The single-block forward (or backward) pallas_call of one static
    configuration, under ``jax.jit(inline=True)`` and built once. Pallas
    traces a kernel anew on every call (~0.17 s of host time), so a
    model's layers would pay that in every program's set-up; the jitted
    call traces once per configuration and, inlined, keeps each call
    site's op name (the layer's scope). ``dtypes``: of dQ, dK, dV (the
    forward's output takes the first)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    nbh = _nbh_for(h, l_pad)
    spec, spec_mask, spec_row = _onekv_specs(nbh, l_pad, d, h)
    in_specs = [spec, spec, spec, spec_mask, spec_mask]
    params = dict(scale=1.0 / (d ** 0.5), nbh=nbh, drop=drop)
    if backward:
        kernel, name = _onekv_bwd_kernel, "flash_bwd_onekv"
        in_specs += [spec, spec_row, spec_row]
        out_specs = [spec, spec, spec]
        out_shape = [jax.ShapeDtypeStruct((b * h, l_pad, d), t)
                     for t in dtypes]
    else:
        kernel, name = _onekv_fwd_kernel, "flash_fwd_onekv"
        params["heads"] = h
        out_specs = [spec, spec_row]
        out_shape = [jax.ShapeDtypeStruct((b * h, l_pad, d), dtypes[0]),
                     jax.ShapeDtypeStruct((b * h, 1, l_pad), jnp.float32)]
    if drop is not None:
        spec_smem, spec_keep, keep_shape = _dropout_specs(drop, nbh, l_pad,
                                                          b * h)
        if backward:
            in_specs.append(spec_keep)
        else:
            in_specs += [spec_smem, spec_smem]     # key words, extents
            out_specs.append(spec_keep)
            out_shape.append(keep_shape)
    return jax.jit(pl.pallas_call(
        functools.partial(kernel, **params),
        name=name,
        grid=(b * h // nbh,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    ), inline=True)


def _onekv_specs(nbh, l_pad, d, h):
    """(row spec, mask spec) for grid (b*h // nbh,): cell g covers bh rows
    [g*nbh, (g+1)*nbh), all in batch row (g*nbh)//h."""
    from jax.experimental import pallas as pl
    spec = pl.BlockSpec((nbh, l_pad, d), lambda g: (g, 0, 0))
    spec_mask = pl.BlockSpec((1, 1, l_pad), lambda g: (g * nbh // h, 0, 0))
    spec_row = pl.BlockSpec((nbh, 1, l_pad), lambda g: (g, 0, 0))
    return spec, spec_mask, spec_row


_FLASH_VJP = None


def _build_vjp():
    """custom_vjp built on first use (keeps this module importable
    without jax)."""
    global _FLASH_VJP
    if _FLASH_VJP is not None:
        return _FLASH_VJP
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
    def fa(q, k, v, kv_mask, q_mask, key, rate, interpret):
        return fa_fwd(q, k, v, kv_mask, q_mask, key, rate, interpret)[0]

    def fa_fwd(q, k, v, kv_mask, q_mask, key, rate, interpret):
        out, lse, keep = flash_attention_fwd(
            q, k, v, kv_mask, interpret=interpret, q_mask=q_mask,
            dropout_rate=rate, dropout_key=key)
        return out, (q, k, v, kv_mask, q_mask, out, lse, keep)

    def fa_bwd(rate, interpret, residuals, ct):
        q, k, v, kv_mask, q_mask, out, lse, keep = residuals
        dq, dk, dv = flash_attention_bwd(q, k, v, kv_mask, out, lse, ct,
                                         interpret=interpret, q_mask=q_mask,
                                         dropout_rate=rate, keep=keep)
        return dq, dk, dv, None, None, None

    fa.defvjp(fa_fwd, fa_bwd)
    _FLASH_VJP = fa
    return fa


def flash_attention(q, k, v, kv_mask=None, interpret=None, q_mask=None,
                    segments=None, dropout_rate=0.0, dropout_rng=None):
    """Differentiable fused attention: pallas forward AND backward (see
    module docstring).

    ``kv_mask`` [B, L] is strictly a BINARY key-padding mask (1 = attend);
    any nonzero value normalizes to 1. For packed rows pass per-token
    segment ids as ``segments=`` — it sets both sides and attention
    becomes block-diagonal per segment (0 = padding). Passing segment ids
    as ``kv_mask`` alone would silently attend across packed segments
    (ADVICE r4), which is why the packed path has its own keyword;
    ``q_mask`` stays for callers composing the two sides explicitly.

    ``dropout_rate`` > 0 (single-block shapes only) drops attention
    probabilities with the mask ``jax.random.bernoulli(dropout_rng, 1 -
    dropout_rate, (B, H, L, L))`` draws, bit for bit within each row's
    valid extent (up to its last valid key), so the dense path's
    ``Dropout`` with the same threefry key trains the same model: the
    real tokens' outputs and, with a zero cotangent on padding rows, the
    gradients are the same."""
    if segments is not None:
        if kv_mask is not None or q_mask is not None:
            raise ValueError(
                "segments= is exclusive with kv_mask/q_mask: it defines "
                "both sides of the block-diagonal mask")
        kv_mask, q_mask = segments, segments
    elif kv_mask is None:
        raise ValueError("flash_attention needs kv_mask or segments")
    key = None
    if dropout_rate > 0.0:
        key = threefry_key_words(dropout_rng)
        if key is None:
            raise ValueError("kernel dropout replays threefry2x32 keys only")
    return _build_vjp()(q, k, v, kv_mask, q_mask, key, float(dropout_rate),
                        interpret)
