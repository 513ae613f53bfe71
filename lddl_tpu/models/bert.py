"""BERT for pretraining (MLM + NSP), TPU-first.

The reference ships no models — its "training" is a mock loop
(benchmarks/torch_train.py) that only consumes batches. lddl_tpu includes a
real reference consumer so the loader's contract (shapes, masking, binning)
is exercised by an actual jitted forward/backward on a device mesh, and so
benchmarks can measure end-to-end step time rather than loader time alone.

TPU design notes:
- bf16 activations, fp32 params/optimizer — MXU-native without loss-scale
  bookkeeping.
- Megatron-style tensor parallelism via flax logical axis names:
  QKV/MLP-in are column-parallel ("mlp"/"heads" -> tp), attention-out and
  MLP-out are row-parallel. XLA inserts the psums.
- Sequence parallelism: activations carry a "seq" logical axis; with the
  seq->sp rule, layernorm/embedding/dropout regions run sequence-sharded
  and XLA all-gathers only around attention (the Megatron-SP pattern),
  riding ICI.
- Everything static-shape; the loader's per-bin fixed lengths bound the
  compilation count.
"""

import dataclasses
from typing import Any

import jax.numpy as jnp
import flax.linen as nn

from .attention import Dropout, FeedForward, MultiHeadAttention

# Logical-to-mesh sharding rules (see lddl_tpu.parallel.mesh for axes).
#
# "embed" names PARAM embed dims and maps to fsdp: with an fsdp mesh axis
# the weights and optimizer state live fully sharded (ZeRO-style) and XLA
# all-gathers each weight just-in-time for its matmul. Activations use
# the separate "act_embed" name because their batch dim already rides
# fsdp — one array cannot use the axis twice.
LOGICAL_AXIS_RULES = (
    ("batch", ("dp", "fsdp")),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("act_embed", None),
    ("embed_out", None),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv", None),
    ("vocab", "tp"),
    # Embedding-table ROWS (the token-id dim of nn.Embed tables) shard
    # over fsdp only — "vocab"(tp) there would make every step all-gather
    # the table across tp AND leave the gather output embed-sharded, which
    # XLA can only reshard to (batch, seq) via involuntary full
    # rematerialization (MULTICHIP_r04 warnings). With rows on fsdp the
    # table joins the normal ZeRO just-in-time param gather and the token
    # gather partitions cleanly over the (batch, seq)-sharded indices.
    ("embed_vocab", "fsdp"),
)

with_logical = nn.with_logical_constraint


def axis_rules_for(mesh):
    """LOGICAL_AXIS_RULES restricted to the axes ``mesh`` actually has, so
    one model definition runs on any mesh (dp-only, dp×tp, dp×tp×sp, ...).
    """
    rules = []
    for logical, target in LOGICAL_AXIS_RULES:
        if isinstance(target, tuple):
            present = tuple(a for a in target if a in mesh.axis_names)
            rules.append((logical, present if present else None))
        elif target is not None and target not in mesh.axis_names:
            rules.append((logical, None))
        else:
            rules.append((logical, target))
    return tuple(rules)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16  # activations; params stay fp32
    # "auto" (default): dense at the shortest bins, the pallas flash
    # kernel where it wins or ties (L_pad >= 256 since the round-5
    # single-block kernels; a round-5 chip probe no benchmark cell
    # re-measured) AND computes identical math: with attention_dropout > 0
    # only where the single-block kernels serve (L_pad 256-896 at
    # head_dim 64), which draw the dense path's own keep-mask from a
    # threefry key, and only on a mesh that keeps the batch whole; the
    # choice is per traced sequence length (models.attention.
    # resolve_auto_impl). "dense": all-gather from sp into full-sequence
    # attention (Megatron-SP). "flash": always the pallas kernel; it
    # skips attention-prob dropout where the online kernels serve (L_pad
    # >= 1024 at head_dim 64). "ring": sequence-parallel exact
    # attention — K/V blocks rotate over the sp ring
    # (ops/ring_attention.py), no device ever holds the full sequence;
    # attention-prob dropout is skipped under ring (standard for
    # blockwise kernels). Falls back to dense when the mesh has no sp
    # axis (or sp == 1).
    attention_impl: str = "auto"
    # Rematerialize each encoder layer on the backward pass
    # (jax.checkpoint): activations are recomputed instead of stored,
    # trading ~1/3 more FLOPs for O(num_layers) less activation memory —
    # the standard lever for long sequences / big batches on HBM.
    remat: bool = False
    # Run the MLM head only at the masked positions: the train step
    # gathers the ~15% masked columns (a static cap P, see
    # train.mlm_gather_cap) before the vocab projection, cutting the
    # head's matmul FLOPs and its [B, L, vocab] fp32 logits (the largest
    # tensor of the step, and pure overhead at the ~85% unmasked
    # positions — loss and gradients are IDENTICAL, since unmasked logits
    # never contribute). Direct model.apply calls without
    # masked_positions still produce full [B, L, vocab] logits.
    mlm_gather: bool = True
    # PRNG implementation for the per-step dropout key. "rbg" drives the
    # TPU's hardware RNG through XLA's RngBitGenerator — measured 14.3 ms
    # (15%) off a bert_large L=512 train step vs threefry, which computes
    # the hash chain on the VPU (STEP_PROFILE.json). Dropout masks remain
    # deterministic in (seed, step) for a fixed program, but rbg draws are
    # not guaranteed bit-stable across compiler versions or mesh shapes —
    # set "threefry" if dropout masks must replay exactly everywhere.
    dropout_rng_impl: str = "rbg"

    def __post_init__(self):
        if self.attention_impl not in ("auto", "dense", "ring", "flash"):
            raise ValueError("attention_impl must be auto|dense|ring|flash")
        if self.dropout_rng_impl not in ("rbg", "threefry"):
            raise ValueError("dropout_rng_impl must be rbg|threefry")
        if self.attention_impl in ("ring", "flash") \
                and self.attention_dropout > 0:
            import warnings
            where = ("" if self.attention_impl == "ring" else
                     " where the online kernels serve (L_pad >= 1024 at "
                     "head_dim 64)")
            warnings.warn(
                "attention_impl='{}' skips attention-probability dropout{} "
                "(standard for blockwise kernels): with attention_dropout="
                "{} it trains a slightly different model than 'dense' "
                "there. Set attention_dropout=0.0 to silence this.".format(
                    self.attention_impl, where, self.attention_dropout),
                stacklevel=2)

    @staticmethod
    def bert_base(**kw):
        return BertConfig(**kw)

    @staticmethod
    def bert_large(**kw):
        kw.setdefault("hidden_size", 1024)
        kw.setdefault("num_layers", 24)
        kw.setdefault("num_heads", 16)
        kw.setdefault("intermediate_size", 4096)
        return BertConfig(**kw)

    @staticmethod
    def tiny(**kw):
        """For tests and dryruns."""
        kw.setdefault("vocab_size", 512)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("num_layers", 2)
        kw.setdefault("num_heads", 4)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("max_position_embeddings", 128)
        return BertConfig(**kw)


def _dense_init(cfg):
    return nn.initializers.normal(stddev=cfg.initializer_range)


class Embeddings(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids, deterministic,
                 position_ids=None):
        cfg = self.cfg
        # Embedding tables shard on their ROW (token-id) dim over fsdp
        # only — an embed-dim ("embed"→fsdp) sharding here would propagate
        # into the gather outputs as embed-sharded [B, L, E] activations
        # that XLA cannot reshard to (batch, seq) without involuntary full
        # rematerialization (see LOGICAL_AXIS_RULES).
        word = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed_vocab", None)),
            name="word_embeddings")(input_ids)
        word = with_logical(word, ("batch", "seq", None))
        if position_ids is None:
            position_ids = jnp.arange(input_ids.shape[1])[None, :]
        pos = nn.Embed(
            cfg.max_position_embeddings, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed_vocab", None)),
            name="position_embeddings")(position_ids)
        typ = nn.Embed(
            cfg.type_vocab_size, cfg.hidden_size, dtype=cfg.dtype,
            embedding_init=nn.with_logical_partitioning(
                _dense_init(cfg), (None, None)),
            name="token_type_embeddings")(token_type_ids)
        x = word + pos + typ
        x = with_logical(x, ("batch", "seq", "act_embed"))
        x = nn.LayerNorm(epsilon=self.cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="layer_norm")(x)
        x = Dropout(cfg.hidden_dropout)(x, deterministic=deterministic)
        return x


def _attention(cfg, name):
    """The shared MultiHeadAttention configured from a model config; child
    params named query/key/value/output (stable checkpoint trees)."""
    return MultiHeadAttention(
        hidden_size=cfg.hidden_size,
        num_heads=cfg.num_heads,
        dtype=cfg.dtype,
        dropout=cfg.attention_dropout,
        initializer_range=cfg.initializer_range,
        attention_impl=cfg.attention_impl,
        name=name)


def _feed_forward(cfg, name="ffn"):
    """The shared transformer MLP configured from a model config."""
    return FeedForward(
        hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        dtype=cfg.dtype,
        initializer_range=cfg.initializer_range,
        name=name)


class EncoderLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask, deterministic, segments=None):
        cfg = self.cfg
        attn = _attention(cfg, "attention")(x, x, attention_mask,
                                            deterministic,
                                            segments=segments)
        attn = Dropout(cfg.hidden_dropout)(attn, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="attention_norm")(x + attn)

        h = _feed_forward(cfg)(x)
        h = Dropout(cfg.hidden_dropout)(h, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="ffn_norm")(x + h)
        return with_logical(x, ("batch", "seq", "act_embed"))


class BertForPreTraining(nn.Module):
    """Encoder + MLM head + NSP head.

    Unpacked: returns (mlm_logits [B,L,vocab], nsp_logits [B,2]) in fp32.

    Packed rows (sequence packing, ops/packing.py): pass ``segments``
    [B,L] (per-token pack slot id, 0 = pad — attention becomes
    block-diagonal), ``position_ids`` [B,L] (restart at each packed
    sample, so every sample sees the same positions it would unpacked)
    and ``cls_positions`` [B,P] (each packed sample's [CLS] column);
    nsp_logits is then [B,P,2]. Params are identical either way — the
    same checkpoint serves packed and unpacked training.
    """
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids, attention_mask,
                 segments=None, position_ids=None, cls_positions=None,
                 deterministic=True, masked_positions=None):
        cfg = self.cfg
        x = Embeddings(cfg, name="embeddings")(
            input_ids, token_type_ids, deterministic,
            position_ids=position_ids)
        layer_cls = (nn.remat(EncoderLayer, static_argnums=(3,))
                     if cfg.remat else EncoderLayer)
        for i in range(cfg.num_layers):
            x = layer_cls(cfg, name="layer_{}".format(i))(
                x, attention_mask, deterministic, segments)

        # MLM head: transform + tied-free decoder to vocab (column-parallel).
        # With masked_positions [B, P] only those columns are projected
        # (mlm_logits [B, P, vocab]); loss-equivalent to the full head
        # because unmasked logits never enter the loss (see cfg.mlm_gather).
        if masked_positions is not None:
            xm = jnp.take_along_axis(x, masked_positions[:, :, None], axis=1)
            xm = with_logical(xm, ("batch", None, "act_embed"))
        else:
            xm = x
        h = nn.Dense(
            cfg.hidden_size, dtype=cfg.dtype,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed", "embed_out")),
            name="mlm_transform")(xm)
        h = nn.gelu(h, approximate=True)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         name="mlm_norm")(h)
        mlm_logits = nn.Dense(
            cfg.vocab_size, dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed", "vocab")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("vocab",)),
            name="mlm_decoder")(h)

        # NSP head over the [CLS] position(s): [B,0] unpacked, or every
        # packed sample's own [CLS] column.
        if cls_positions is None:
            cls_states = x[:, 0]                       # [B, H]
        else:
            cls_states = jnp.take_along_axis(           # [B, P, H]
                x, cls_positions[:, :, None], axis=1)
        pooled = nn.tanh(
            nn.Dense(
                cfg.hidden_size, dtype=cfg.dtype,
                kernel_init=nn.with_logical_partitioning(
                    _dense_init(cfg), ("embed", "embed_out")),
                name="pooler")(cls_states))
        nsp_logits = nn.Dense(
            2, dtype=jnp.float32,
            kernel_init=nn.with_logical_partitioning(
                _dense_init(cfg), ("embed", None)),
            name="nsp_classifier")(pooled)
        return mlm_logits, nsp_logits


class BertForPreTrainingPacked(BertForPreTraining):
    """BertForPreTraining bound to the packed-batch key order (same params;
    see the base class docstring and loader/bert.py packed collate)."""

    BATCH_INPUTS = ("input_ids", "token_type_ids", "attention_mask",
                    "segments", "position_ids", "cls_positions")
