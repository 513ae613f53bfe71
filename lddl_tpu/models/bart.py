"""BART-style encoder-decoder for denoising pretraining, TPU-first.

The reference preprocesses BART chunks but ships neither a BART loader
nor any model (SURVEY.md §2.3/§2.5); lddl_tpu completes the path:
loader/bart.py emits ``{input_ids, attention_mask, decoder_input_ids,
labels}`` batches, and this model consumes them — so the BART contract
is exercised by a real jitted encoder-decoder forward/backward on a
device mesh, exactly as models/bert.py does for the BERT contract.

Sharding follows the same logical-axis scheme as models/bert.py
(LOGICAL_AXIS_RULES): Megatron-style column/row-parallel projections
over tp, batch over dp/fsdp, activations sequence-sharded over sp
between blocks with gathers around attention. bf16 activations, fp32
params. Decoder self-attention is causal; cross-attention keys off the
encoder output.
"""

import dataclasses
from typing import Any

import jax.numpy as jnp
import flax.linen as nn

from .attention import Dropout
from .bert import (  # noqa: F401 (shared rules)
    LOGICAL_AXIS_RULES,
    _attention,
    _feed_forward,
    axis_rules_for,
    with_logical,
)


@dataclasses.dataclass(frozen=True)
class BartConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_encoder_layers: int = 6
    num_decoder_layers: int = 6
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    # "auto"/"flash"/"ring" engage blockwise attention for the ENCODER's
    # bidirectional self-attention only (models/attention.py); the
    # decoder's causal self-attention and the cross-attention stay dense.
    # See BertConfig.attention_impl for the auto selection rule.
    attention_impl: str = "auto"
    # Rematerialize encoder/decoder layers on backward (jax.checkpoint):
    # ~1/3 more FLOPs for O(num_layers) less activation memory.
    remat: bool = False
    # Dropout PRNG implementation; see BertConfig.dropout_rng_impl.
    dropout_rng_impl: str = "rbg"

    def __post_init__(self):
        if self.attention_impl not in ("auto", "dense", "ring", "flash"):
            raise ValueError("attention_impl must be auto|dense|ring|flash")
        if self.dropout_rng_impl not in ("rbg", "threefry"):
            raise ValueError("dropout_rng_impl must be rbg|threefry")

    @staticmethod
    def bart_base(**kw):
        return BartConfig(**kw)

    @staticmethod
    def tiny(**kw):
        """For tests and dryruns."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_encoder_layers", 2)
        kw.setdefault("num_decoder_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_position_embeddings", 128)
        return BartConfig(**kw)


def _dense_init(cfg):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class Embeddings(nn.Module):
    """Shared token embedding + learned positions (one instance each for
    encoder and decoder inputs; the token table is shared via the parent
    passing the same module)."""

    cfg: BartConfig

    @nn.compact
    def __call__(self, token_embed, input_ids, deterministic):
        cfg = self.cfg
        x = token_embed(input_ids)
        x = with_logical(x, ("batch", "seq", None))
        pos = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed_vocab", None)),
            name="positions")(jnp.arange(input_ids.shape[1])[None, :])
        x = with_logical(x + pos, ("batch", "seq", "act_embed"))
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="layer_norm")(x)
        return Dropout(cfg.hidden_dropout)(x, deterministic=deterministic)


def causal_bias(length):
    """[1, 1, L, L] additive causal mask (finite -1e9, see
    models/attention.py)."""
    tri = jnp.tril(jnp.ones((length, length), jnp.bool_))
    return jnp.where(tri, 0.0, -1e9)[None, None, :, :]


class EncoderLayer(nn.Module):
    cfg: BartConfig

    @nn.compact
    def __call__(self, x, padding_mask, deterministic):
        cfg = self.cfg
        a = _attention(cfg, "self_attention")(x, x, padding_mask,
                                              deterministic)
        a = Dropout(cfg.hidden_dropout)(a, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="self_norm")(x + a)
        h = _feed_forward(cfg)(x)
        h = Dropout(cfg.hidden_dropout)(h, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ffn_norm")(x + h)
        return with_logical(x, ("batch", "seq", "act_embed"))


class DecoderLayer(nn.Module):
    cfg: BartConfig

    @nn.compact
    def __call__(self, x, enc, self_bias, enc_padding_mask, deterministic):
        cfg = self.cfg
        # Causal self-attention (extra_bias forces the dense path; ring is
        # bidirectional-only).
        a = _attention(cfg, "self_attention")(x, x, None, deterministic,
                                              extra_bias=self_bias)
        a = Dropout(cfg.hidden_dropout)(a, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="self_norm")(x + a)
        c = _attention(cfg, "cross_attention")(x, enc, enc_padding_mask,
                                               deterministic)
        c = Dropout(cfg.hidden_dropout)(c, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="cross_norm")(x + c)
        h = _feed_forward(cfg)(x)
        h = Dropout(cfg.hidden_dropout)(h, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ffn_norm")(x + h)
        return with_logical(x, ("batch", "seq", "act_embed"))


class BartForPreTraining(nn.Module):
    """Encoder-decoder + LM head over the decoder states.

    Consumes the loader/bart.py batch contract positionally (see
    BATCH_INPUTS); returns fp32 logits [B, L_dec, vocab].
    """

    cfg: BartConfig
    BATCH_INPUTS = ("input_ids", "attention_mask", "decoder_input_ids")

    @nn.compact
    def __call__(self, input_ids, attention_mask, decoder_input_ids,
                 deterministic=True):
        cfg = self.cfg
        # Rows on fsdp, embed dim replicated — same rationale as the BERT
        # Embeddings tables (gather outputs must come out (batch, seq)-
        # sharded, not embed-sharded; see bert.LOGICAL_AXIS_RULES).
        token_embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed_vocab", None)),
            name="shared_embeddings")

        enc_cls = (nn.remat(EncoderLayer, static_argnums=(3,))
                   if cfg.remat else EncoderLayer)
        dec_cls = (nn.remat(DecoderLayer, static_argnums=(5,))
                   if cfg.remat else DecoderLayer)
        x = Embeddings(cfg, name="encoder_embed")(
            token_embed, input_ids, deterministic)
        for i in range(cfg.num_encoder_layers):
            x = enc_cls(cfg, name="encoder_{}".format(i))(
                x, attention_mask, deterministic)
        enc = x

        self_bias = causal_bias(decoder_input_ids.shape[1])
        y = Embeddings(cfg, name="decoder_embed")(
            token_embed, decoder_input_ids, deterministic)
        for i in range(cfg.num_decoder_layers):
            y = dec_cls(cfg, name="decoder_{}".format(i))(
                y, enc, self_bias, attention_mask, deterministic)

        logits = nn.Dense(
            cfg.vocab_size, dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed", "vocab")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("vocab",)),
            name="lm_head")(y)
        return logits


def bart_batch_loss(logits, batch, ignore_index=-1):
    """Denoising CE over the clean labels (ignore_index on padding) ->
    (loss, metrics). The batch_loss adapter for models.train."""
    import optax

    labels = batch["labels"]
    mask = labels != ignore_index
    safe = jnp.where(mask, labels, 0)
    ll = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    denom = jnp.maximum(mask.sum(), 1)
    loss = jnp.where(mask, ll, 0.0).sum() / denom
    correct = jnp.where(mask, jnp.argmax(logits, -1) == safe, False)
    return loss, {
        "loss": loss,
        "accuracy": correct.sum() / denom,
    }
