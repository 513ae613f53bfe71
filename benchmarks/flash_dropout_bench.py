"""Attention-probability dropout at one shape: the single-block flash
kernels, which draw the keep-mask themselves, against the model's dense
path (XLA softmax and the stored ``Dropout`` mask) with the same
threefry key.

Each timed dispatch runs ``--iters`` iterations inside one ``lax.scan``
(the key folds in the iteration, so no hash is loop-invariant), so the
per-dispatch host overhead is amortized away. Prints one JSON line of
per-iteration milliseconds (best of ``--reps`` dispatches) and the
forward's largest deviation from the dense path; ``--out`` also writes it
to a file.

Usage: python benchmarks/flash_dropout_bench.py [--batch 16 --heads 16
    --seq-len 512 --head-dim 64 --rate 0.1 --iters 20 --reps 3 --out F]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _dense(q, k, v, mask, key, rate):
    """models/attention.py's dense branch: bf16 scores and softmax, the
    stored-mask Dropout, bf16 context."""
    import jax
    import jax.numpy as jnp
    from lddl_tpu.models.attention import Dropout

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        q.shape[-1]).astype(q.dtype)
    bias = jnp.where(mask[:, None, None, :] > 0, 0.0, -1e9)
    p = jax.nn.softmax(s + bias.astype(q.dtype), axis=-1)
    p = Dropout(rate).apply({}, p, deterministic=rate == 0.0, rng=key)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _flash(q, k, v, mask, key, rate):
    from lddl_tpu.ops.flash_attention import flash_attention
    return flash_attention(q, k, v, mask, dropout_rate=rate,
                           dropout_rng=key)


def _loop(attn, mask, key, rate, iters, backward):
    """lax.scan of ``iters`` forward (or forward+backward) iterations; the
    output (or gradients) fold into the carry so nothing is dead code."""
    import jax
    import jax.numpy as jnp

    def step(c, i):
        q, k, v = c
        key_i = jax.random.fold_in(key, i)

        def loss(q, k, v):
            out = attn(q, k, v, mask, key_i, rate)
            return (out.astype(jnp.float32) ** 2).sum(), out

        if backward:
            (l, _), (dq, dk, dv) = jax.value_and_grad(
                loss, (0, 1, 2), has_aux=True)(q, k, v)
            return (q + dq.astype(q.dtype) * 1e-6,
                    k + dk.astype(k.dtype) * 1e-6,
                    v + dv.astype(v.dtype) * 1e-6), l
        l, out = loss(q, k, v)
        return (q + out * 1e-6, k, v), l

    def run(q, k, v):
        _, ls = jax.lax.scan(step, (q, k, v), jnp.arange(iters))
        return ls[-1]

    return jax.jit(run)


def _best_ms(fn, args, reps, iters):
    fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / iters * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--rate", type=float, default=0.1)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from lddl_tpu.utils.rng import sample_rng

    b, l, h, d = args.batch, args.seq_len, args.heads, args.head_dim
    g = sample_rng(0)
    q, k, v = (jnp.asarray(g.standard_normal((b, l, h, d)), jnp.bfloat16)
               for _ in range(3))
    mask = np.ones((b, l), np.int32)
    mask[0, l - l // 8:] = 0
    mask = jnp.asarray(mask)
    key = jax.random.PRNGKey(1)

    out_f = jax.jit(_flash, static_argnums=5)(q, k, v, mask, key, args.rate)
    out_d = jax.jit(_dense, static_argnums=5)(q, k, v, mask, key, args.rate)
    err = float(jnp.abs(out_f.astype(jnp.float32)
                        - out_d.astype(jnp.float32)).max())

    ms = {}
    for name, attn, rate, backward in [
            ("flash_fwd", _flash, 0.0, False),
            ("flash_fwd_dropout", _flash, args.rate, False),
            ("flash_fwd_bwd", _flash, 0.0, True),
            ("flash_fwd_bwd_dropout", _flash, args.rate, True),
            ("dense_fwd_dropout", _dense, args.rate, False),
            ("dense_fwd_bwd_dropout", _dense, args.rate, True)]:
        fn = _loop(attn, mask, key, rate, args.iters, backward)
        ms[name] = _best_ms(fn, (q, k, v), args.reps, args.iters)
    line = json.dumps({"device": str(jax.devices()[0]),
                       "shape": [b, l, h, d], "rate": args.rate,
                       "iters": args.iters, "reps": args.reps,
                       "fwd_max_abs_dev_vs_dense": err, "ms": ms})
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
