"""Weights from the seed, in the reference's layout and in the program's.

The benchmark makes the weights, not the program: the plain reference
must not take anything the program made. ``make_ref_params`` draws them
from the seed in the reference's layout (encoder layers stacked on a
leading axis); ``to_program_params`` lays the same arrays out as the
program's parameter tree (``lddl_tpu.models.bert.BertForPreTraining``'s
flax names). A parameter the program renames or adds makes its first
step fail; one it drops leaves the reference computing with it, and the
comparison fails.

Initialisation is BERT's: N(0, 0.02) for every matrix and embedding,
zeros for biases, ones for LayerNorm scales. All float32.
"""

import jax
import jax.numpy as jnp

LAYER_KEYS = ("q_w", "q_b", "k_w", "k_b", "v_w", "v_b", "o_w", "o_b",
              "ln1_g", "ln1_b", "ff1_w", "ff1_b", "ff2_w", "ff2_b",
              "ln2_g", "ln2_b")


def ref_shapes(cfg):
    """{name: shape} of the reference layout. ``cfg`` is the model
    section of a configuration file (hidden_size, num_hidden_layers, ...)."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    layer = {"q_w": (h, h), "q_b": (h,), "k_w": (h, h), "k_b": (h,),
             "v_w": (h, h), "v_b": (h,), "o_w": (h, h), "o_b": (h,),
             "ln1_g": (h,), "ln1_b": (h,), "ff1_w": (h, f), "ff1_b": (f,),
             "ff2_w": (f, h), "ff2_b": (h,), "ln2_g": (h,), "ln2_b": (h,)}
    shapes = {k: (n,) + s for k, s in layer.items()}
    shapes.update({
        "word_emb": (v, h),
        "pos_emb": (cfg["max_position_embeddings"], h),
        "type_emb": (cfg["type_vocab_size"], h),
        "emb_ln_g": (h,), "emb_ln_b": (h,),
        "mlm_w": (h, h), "mlm_b": (h,), "mlm_ln_g": (h,), "mlm_ln_b": (h,),
        "dec_w": (h, v), "dec_b": (v,),
        "pool_w": (h, h), "pool_b": (h,),
        "nsp_w": (h, 2), "nsp_b": (2,),
    })
    return shapes


def seed_key(seed):
    """A PRNG key from any non-negative seed (seeds may exceed 32
    bits): the low 31 bits make the key, the rest is folded in."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_ref_params(cfg, key):
    """Weights drawn from ``key`` in the reference layout. Traceable, so
    callers jit it (one call on the device)."""
    std = cfg["initializer_range"]
    out = {}
    for i, (name, shape) in enumerate(sorted(ref_shapes(cfg).items())):
        if name.endswith("_g"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            out[name] = std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
    return out


def _dense(w, b):
    return {"kernel": w, "bias": b}


def _norm(g, b):
    return {"scale": g, "bias": b}


def to_program_params(cfg, p):
    """The reference-layout tree ``p`` as the program's parameter tree.
    Works on any tree of that layout: weights, gradients, moments."""
    tree = {
        "embeddings": {
            "word_embeddings": {"embedding": p["word_emb"]},
            "position_embeddings": {"embedding": p["pos_emb"]},
            "token_type_embeddings": {"embedding": p["type_emb"]},
            "layer_norm": _norm(p["emb_ln_g"], p["emb_ln_b"]),
        },
        "mlm_transform": _dense(p["mlm_w"], p["mlm_b"]),
        "mlm_norm": _norm(p["mlm_ln_g"], p["mlm_ln_b"]),
        "mlm_decoder": _dense(p["dec_w"], p["dec_b"]),
        "pooler": _dense(p["pool_w"], p["pool_b"]),
        "nsp_classifier": _dense(p["nsp_w"], p["nsp_b"]),
    }
    for i in range(cfg["num_hidden_layers"]):
        q = {k: p[k][i] for k in LAYER_KEYS}
        tree["layer_{}".format(i)] = {
            "attention": {
                "query": _dense(q["q_w"], q["q_b"]),
                "key": _dense(q["k_w"], q["k_b"]),
                "value": _dense(q["v_w"], q["v_b"]),
                "output": _dense(q["o_w"], q["o_b"]),
            },
            "attention_norm": _norm(q["ln1_g"], q["ln1_b"]),
            "ffn": {
                "intermediate": _dense(q["ff1_w"], q["ff1_b"]),
                "output": _dense(q["ff2_w"], q["ff2_b"]),
            },
            "ffn_norm": _norm(q["ln2_g"], q["ln2_b"]),
        }
    return tree


def leaf_norms(tree):
    """{"a/b/c": float32 norm} of every leaf, as one traceable dict."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in flat}
