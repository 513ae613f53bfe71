"""Plain float32 BERT pretraining: forward, MLM + NSP loss, gradient,
global-norm clipping and AdamW, in ``jax.numpy``.

Independent of the program under test: it imports nothing of it and is
given nothing it made. It follows Devlin et al. 2018 (post-LN encoder,
GELU in its tanh form as in the published ``modeling.py``, LayerNorm eps
1e-12) with the program's departures noted:

- the MLM decoder is its own [hidden, vocab] matrix with a bias, not tied
  to the word embeddings (the program's head is untied);
- padded keys get a -1e9 additive bias before the softmax;
- dropout (the published 0.1 on the embeddings, the attention
  probabilities and each sub-layer's output) draws its masks as the
  program's flax modules do with its ``dropout_rng_impl="threefry"``
  option: the step's key is the seed's ``PRNGKey`` with the step number
  folded in, and each dropout site folds in the first four bytes of the
  SHA-1 of its module path and call count (``dropout_keys``), then keeps
  an element where ``jax.random.bernoulli(key, 1 - rate, shape)`` is true.

Precision: ``"f32"`` multiplies at ``highest`` (true float32 on a TPU).
``"fp8"`` is the control, the configuration's precision one step down:
every tensor the configuration holds in bfloat16 (each matrix product's
operands and each activation: embeddings, projections, attention scores,
probabilities and context, sub-layer outputs, residual sums, LayerNorm
and GELU outputs) is rounded to float8 e4m3 with one scale per tensor;
parameters, gradients, optimizer state and the logits stay float32, as
the configuration states. The batch is
processed in blocks of rows and the encoder layers are rematerialised,
so that the reference fits on the chip beside nothing else at BERT-large
L=512.
"""

import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from ..weights import LAYER_KEYS, make_ref_params

E4M3_MAX = 448.0
IGNORE = -1


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor (straight-through:
    the backward pass sees the rounded operand's value)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _ops(precision):
    """(matrix product, einsum, activation rounding) at ``precision``."""
    q = _fp8 if precision == "fp8" else (lambda x: x)

    def mm(x, w):
        return jnp.matmul(q(x), q(w))

    def ein(spec, a, b):
        return jnp.einsum(spec, q(a), q(b))
    return mm, ein, q


def _ln(x, g, b, eps=1e-12):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _site_hash(path):
    """The first four bytes of the SHA-1 of a dropout site's module path
    and call count, big-endian."""
    m = hashlib.sha1()
    for x in path:
        m.update(x.encode("utf-8") if isinstance(x, str)
                 else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return np.uint32(int.from_bytes(m.digest()[:4], "big"))


def dropout_keys(seed, step, n_layers):
    """The dropout keys of step ``step`` (0 for the first) of a run whose
    steps take ``seed``: the embeddings' key, and the per-layer keys of
    the attention probabilities, the attention output and the feed-forward
    output, stacked on a leading layer axis."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), step)

    def key(*path):
        return jax.random.fold_in(base, _site_hash(path + (1,)))
    layers = ["layer_{}".format(i) for i in range(n_layers)]
    return {
        "emb": key("embeddings", "Dropout_0"),
        "probs": jnp.stack([key(n, "attention", "Dropout_0") for n in layers]),
        "attn_out": jnp.stack([key(n, "Dropout_0") for n in layers]),
        "ffn_out": jnp.stack([key(n, "Dropout_1") for n in layers]),
    }


def _dropout(x, key, rate, shape, row0):
    """``x`` (a block of rows starting at ``row0``) with the rows of the
    mask drawn at the whole batch's ``shape`` applied."""
    if rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, shape)
    mask = jax.lax.dynamic_slice_in_dim(mask, row0, x.shape[0], axis=0)
    return jnp.where(mask, x / keep, 0.0)


def _xent(logits, labels):
    """Cross entropy per position (labels already made safe)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def block_sums(p, blk, keys, row0, n_rows, cfg, precision):
    """(sum of masked-LM cross entropy, sum of NSP cross entropy) over a
    block of rows starting at row ``row0`` of a batch of ``n_rows``.
    ``blk`` holds input_ids, token_type_ids, attention_mask, labels [b, L]
    and next_sentence_labels [b]; ``keys`` are the step's dropout keys."""
    mm, ein, act = _ops(precision)
    n_heads = cfg["num_attention_heads"]
    p_hidden = cfg["hidden_dropout_prob"]
    p_probs = cfg["attention_probs_dropout_prob"]
    ids, typ = blk["input_ids"], blk["token_type_ids"]
    mask = blk["attention_mask"]
    b, L = ids.shape
    h = p["word_emb"].shape[1]
    dh = h // n_heads
    hidden_shape = (n_rows, L, h)

    x = act(p["word_emb"][ids] + p["pos_emb"][:L][None]
            + p["type_emb"][typ])
    x = act(_ln(x, p["emb_ln_g"], p["emb_ln_b"]))
    x = _dropout(x, keys["emb"], p_hidden, hidden_shape, row0)
    key_bias = jnp.where(mask > 0, 0.0, -1e9)[:, None, None, :]

    def layer(x, qk):
        q, k = qk

        def heads(t):
            return t.reshape(b, L, n_heads, dh)
        qh = heads(act(mm(x, q["q_w"]) + q["q_b"]))
        kh = heads(act(mm(x, q["k_w"]) + q["k_b"]))
        vh = heads(act(mm(x, q["v_w"]) + q["v_b"]))
        scores = act(ein("bqhd,bkhd->bhqk", qh, kh) / jnp.sqrt(float(dh)))
        probs = act(jax.nn.softmax(scores + key_bias, axis=-1))
        probs = _dropout(probs, k["probs"], p_probs, (n_rows, n_heads, L, L),
                         row0)
        ctx = act(ein("bhqk,bkhd->bqhd", probs, vh)).reshape(b, L, h)
        a = _dropout(act(mm(ctx, q["o_w"]) + q["o_b"]), k["attn_out"],
                     p_hidden, hidden_shape, row0)
        x = act(_ln(act(x + a), q["ln1_g"], q["ln1_b"]))
        f = act(_gelu(act(mm(x, q["ff1_w"]) + q["ff1_b"])))
        f = _dropout(act(mm(f, q["ff2_w"]) + q["ff2_b"]), k["ffn_out"],
                     p_hidden, hidden_shape, row0)
        return act(_ln(act(x + f), q["ln2_g"], q["ln2_b"])), None

    stacked = {k: p[k] for k in LAYER_KEYS}
    layer_keys = {k: v for k, v in keys.items() if k != "emb"}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, (stacked, layer_keys))

    t = act(_gelu(act(mm(x, p["mlm_w"]) + p["mlm_b"])))
    t = act(_ln(t, p["mlm_ln_g"], p["mlm_ln_b"]))
    logits = mm(t, p["dec_w"]) + p["dec_b"]
    labels = blk["labels"]
    masked = labels != IGNORE
    mlm = jnp.sum(jnp.where(masked, _xent(logits, jnp.where(masked, labels,
                                                           0)), 0.0))
    pooled = act(jnp.tanh(act(mm(x[:, 0], p["pool_w"]) + p["pool_b"])))
    nsp_logits = mm(pooled, p["nsp_w"]) + p["nsp_b"]
    nsp = jnp.sum(_xent(nsp_logits, blk["next_sentence_labels"]))
    return mlm, nsp


def loss_and_grad(p, batch, keys, cfg, precision, block_rows):
    """(loss, gradient) of the mean masked-LM loss plus the mean NSP loss
    over ``batch``, accumulated over blocks of ``block_rows`` rows."""
    n_rows = batch["input_ids"].shape[0]
    n_blocks = n_rows // block_rows
    n_masked = jnp.maximum(jnp.sum(batch["labels"] != IGNORE), 1)
    blocks = jax.tree.map(
        lambda a: a.reshape((n_blocks, block_rows) + a.shape[1:]), batch)

    def body(carry, blk_row0):
        blk, row0 = blk_row0

        def f(p):
            mlm, nsp = block_sums(p, blk, keys, row0, n_rows, cfg, precision)
            return mlm / n_masked + nsp / n_rows
        val, g = jax.value_and_grad(f)(p)
        loss, acc = carry
        return (loss + val, jax.tree.map(jnp.add, acc, g)), None

    zero = jax.tree.map(jnp.zeros_like, p)
    row0s = jnp.arange(n_blocks, dtype=jnp.int32) * block_rows
    (loss, grad), _ = jax.lax.scan(body, (jnp.float32(0), zero),
                                   (blocks, row0s))
    return loss, grad


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


def adamw_update(p, g, mu, nu, count, opt):
    """One step of clip-by-global-norm followed by AdamW with a linear
    warm-up and cosine decay of the learning rate (decoupled weight decay
    on every parameter). Returns (p, mu, nu, clipped gradient)."""
    gn = _global_norm(g)
    scale = jnp.where(gn < opt["clip_norm"], 1.0, opt["clip_norm"] / gn)
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c = (count + 1).astype(jnp.float32)
    warm = opt["warmup_steps"]
    decay = max(opt["total_steps"], warm + 1) - warm
    t = count.astype(jnp.float32)
    cosine = 0.5 * opt["learning_rate"] * (
        1 + jnp.cos(jnp.pi * jnp.clip((t - warm) / decay, 0.0, 1.0)))
    lr = jnp.where(t < warm, opt["learning_rate"] * t / warm, cosine)

    def upd(p_, m, v):
        u = (m / (1 - b1 ** c)) / (jnp.sqrt(v / (1 - b2 ** c)) + 1e-8)
        return p_ - lr * (u + opt["weight_decay"] * p_)
    return jax.tree.map(upd, p, mu, nu), mu, nu, g


def _items(d):
    return tuple(sorted((k, v) for k, v in d.items()
                        if isinstance(v, (int, float, str))))


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _init(key, cfg_items):
    return make_ref_params(dict(cfg_items), key)


@functools.partial(jax.jit, static_argnames=("cfg_items", "opt_items",
                                             "precision", "block_rows"))
def _step(p, mu, nu, count, batch, keys, cfg_items, opt_items, precision,
          block_rows):
    cfg, opt = dict(cfg_items), dict(opt_items)
    loss, g = loss_and_grad(p, batch, keys, cfg, precision, block_rows)
    p, mu, nu, clipped = adamw_update(p, g, mu, nu, count, opt)
    return p, mu, nu, loss, _global_norm(g), g, clipped


@jax.jit
def _change(p, p0):
    return jax.tree.map(jnp.subtract, p, p0)


def reference_steps(cfg, opt, key, batches, step_seed, precision="f32",
                    block_rows=8):
    """Run the reference over a list of host batches (numpy dicts, each at
    its own length) from the weights that ``key`` gives, step ``i`` with
    the dropout keys of ``step_seed`` and ``i``. Returns (losses, global
    gradient norms, first raw gradient, first clipped gradient, parameter
    change after the last step); the trees are in the reference layout."""
    cfg_items, opt_items = _items(cfg), _items(opt)
    with jax.default_matmul_precision("highest"):
        p0 = _init(key, cfg_items)
        p = p0
        mu = jax.tree.map(jnp.zeros_like, p)
        nu = jax.tree.map(jnp.zeros_like, p)
        losses, gnorms, raw_g1, g1 = [], [], None, None
        for i, batch in enumerate(batches):
            keys = dropout_keys(step_seed, i, cfg["num_hidden_layers"])
            p, mu, nu, loss, gn, raw, clipped = _step(
                p, mu, nu, jnp.int32(i), batch, keys, cfg_items, opt_items,
                precision, block_rows)
            losses.append(loss)
            gnorms.append(gn)
            if i == 0:
                raw_g1, g1 = raw, clipped
            del raw, clipped
        return (jnp.stack(losses), jnp.stack(gnorms), raw_g1, g1,
                _change(p, p0))
