"""Shared multi-head attention for the model stack.

One implementation serves BERT self-attention, BART encoder/decoder
self-attention, and BART cross-attention — so the sharding annotations
(Megatron column/row-parallel over tp), the finite -1e9 masking invariant
(dtype-min overflows to -inf in bf16 and NaNs an all-masked row), and the
ring-attention opt-in live in exactly one place.

Ring attention (ops/ring_attention.py) engages when ``attention_impl ==
"ring"``, the call is self-attention (q_input is kv_input), there is no
extra additive bias (ring is bidirectional-full-attention only — causal
decoding stays dense), and the ambient mesh has sp > 1.
"""

from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from .. import observability as obs
from ..ops.flash_attention import (flash_attention, kernel_dropout_serves,
                                   pad_seq_len, replays_bernoulli,
                                   single_block_serves, threefry_key_words)

with_logical = nn.with_logical_constraint

# Trace-time counter: one per attention call traced into a program, by
# the path taken (dense|flash|ring) and whether it drops probabilities.
IMPL_SELECTED = "attention_impl_selected_total"


class Dropout(nn.Dropout):
    """``flax.linen.Dropout`` whose keep-mask is drawn once per step.

    The boolean mask passes through ``jax.lax.optimization_barrier``
    before the select, so XLA materializes it: the backward reads the
    stored mask instead of re-deriving it from the key in each of its
    consumers (with threefry keys XLA otherwise hashes every mask three
    times a step, on the VPU). The key, the ``bernoulli`` draw and the
    arithmetic are flax's, so outputs and gradients are bit-identical to
    ``nn.Dropout``; the cost is one byte per mask element kept until the
    backward. The class keeps flax's name, so the sites are still
    auto-named ``Dropout_<n>`` and every ``make_rng`` path is unchanged.
    """

    def key(self):
        """The key ``__call__`` would draw, for a caller that applies the
        mask itself (the flash kernels); pass it back as ``rng`` if the
        site is applied after all."""
        return self.make_rng(self.rng_collection)

    @nn.compact
    def __call__(self, inputs, deterministic=None, rng=None):
        deterministic = nn.merge_param("deterministic", self.deterministic,
                                       deterministic)
        if self.rate == 0.0 or deterministic:
            return inputs
        # Prevent gradient NaNs in the 1.0 edge case.
        if self.rate == 1.0:
            return jnp.zeros_like(inputs)
        keep_prob = 1.0 - self.rate
        if rng is None:
            rng = self.make_rng(self.rng_collection)
        broadcast_shape = list(inputs.shape)
        for dim in self.broadcast_dims:
            broadcast_shape[dim] = 1
        mask = jax.random.bernoulli(rng, p=keep_prob, shape=broadcast_shape)
        mask = jax.lax.optimization_barrier(mask)
        mask = jnp.broadcast_to(mask, inputs.shape)
        return jax.lax.select(mask, inputs / keep_prob,
                              jnp.zeros_like(inputs))


def resolve_auto_impl(seq_len, blockwise_ok, attention_dropout,
                      deterministic=False, *, head_dim):
    """attention_impl="auto" -> "flash"|"dense". Flash is picked only where
    it computes the SAME math as dense, so auto never changes the trained
    model, only the speed.

    Without attention-probability dropout (or deterministic, where it is
    a no-op): flash from L_pad 256 up. The thresholds come from round-5
    chip probes that no benchmark cell re-measured: the single-block kernels
    (ops/flash_attention.py, fat (b, h)-row cells + one fused backward)
    win or tie from L_pad 256 through 896 (bert_base L=512 45.2 vs 42.2
    wall MFU, bert_large parity; L=768 in-model 46.4 vs 38.7), and the
    online kernels keep their long-L wins (L=1024: 36.3 vs 34.0; L=2048:
    35.6 vs 28.0, round 4). Dense stays ahead at L_pad <= 128 (52.1 vs
    42.1 at the shortest bin), where per-kernel-launch overhead dominates;
    wider heads keep the 512 bound (_use_onekv).

    With dropout: flash only where the single-block kernels serve, since
    they draw the dense path's own keep-mask from the same threefry key
    (the online kernels have no dropout, so L_pad >= 1024 stays dense) —
    and only when the ambient mesh keeps the batch whole: a kernel under
    a dp/fsdp split would count mask elements from its shard's rows, not
    the batch's. A key of another implementation (rbg) cannot be replayed;
    MultiHeadAttention keeps such calls dense."""
    if not blockwise_ok:
        return "dense"
    if attention_dropout == 0.0 or deterministic:
        # single_block_serves is the dispatcher's own predicate (incl. its
        # head-dim gate), so the selector can never promise the
        # single-block regime where flash_attention would fall back to the
        # online kernels.
        return ("flash" if single_block_serves(seq_len, head_dim)
                or pad_seq_len(seq_len) >= 1024 else "dense")
    return ("flash" if single_block_serves(seq_len, head_dim)
            and not _batch_split() else "dense")


def _batch_split():
    """True when the ambient mesh shards the batch (dp or fsdp > 1)."""
    from ..parallel.mesh import DATA_AXES
    shape = jax.sharding.get_abstract_mesh().shape
    return any(shape.get(axis, 1) > 1 for axis in DATA_AXES)


class MultiHeadAttention(nn.Module):
    """softmax(QK^T/sqrt(d) + bias) V with logical-axis sharding.

    ``padding_mask``: [B, Lk] key validity (1 = attend), or None.
    ``extra_bias``: optional additive [*, Lq, Lk] term (e.g. causal).
    Child params are named query/key/value/output, so wrapping modules
    keep stable checkpoint trees.
    """

    hidden_size: int
    num_heads: int
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0
    initializer_range: float = 0.02
    attention_impl: str = "dense"

    @nn.compact
    def __call__(self, q_input, kv_input, padding_mask, deterministic,
                 extra_bias: Optional[Any] = None,
                 segments: Optional[Any] = None):
        head_dim = self.hidden_size // self.num_heads
        init = nn.initializers.normal(stddev=self.initializer_range)

        def proj(name):
            # Column-parallel: the flat (heads*head_dim) output dim shards
            # over tp ("heads"); reshaped to [B, L, H, D] after.
            return nn.Dense(
                self.num_heads * head_dim, dtype=self.dtype,
                kernel_init=nn.with_logical_partitioning(
                    init, ("embed", "heads")),
                bias_init=nn.with_logical_partitioning(
                    nn.initializers.zeros_init(), ("heads",)),
                name=name)

        def split_heads(t, seq_ax):
            t = t.reshape(t.shape[0], t.shape[1], self.num_heads, head_dim)
            return with_logical(t, ("batch", seq_ax, "heads", "kv"))

        # Blockwise impls serve bidirectional self-attention with a plain
        # padding mask; causal/cross calls always take the dense path.
        blockwise_ok = (q_input is kv_input and extra_bias is None
                        and padding_mask is not None)
        # The attention-probability dropout site, Dropout_0 as ever: the
        # dense path applies it, the single-block kernels take its key.
        drop = Dropout(self.dropout)
        rate = 0.0 if deterministic else self.dropout
        impl = self.attention_impl
        if impl == "auto":
            impl = resolve_auto_impl(q_input.shape[1], blockwise_ok,
                                     self.dropout, deterministic,
                                     head_dim=head_dim)
        rng = None
        if (impl == "flash" and blockwise_ok and rate > 0.0
                and kernel_dropout_serves(q_input.shape[1], head_dim)):
            rng = drop.key()
            if self.attention_impl == "auto" and not replays_bernoulli(rng):
                impl = "dense"    # the kernel cannot replay its mask
            elif threefry_key_words(rng) is None:
                rng = jax.random.bits(rng, (2,), jnp.uint32)
        use_ring = False
        if impl == "ring" and blockwise_ok:
            mesh = jax.sharding.get_abstract_mesh()
            use_ring = "sp" in mesh.axis_names and mesh.shape["sp"] > 1
        if segments is not None and use_ring:
            # Packing serves SHORT samples; ring serves LONG sequences —
            # the combination has no use case, so fail loudly rather than
            # silently attending across packed samples.
            raise NotImplementedError(
                "packed sequences (segments) are not supported with ring "
                "attention; use attention_impl='flash' or 'dense'")
        path = ("ring" if use_ring else
                "flash" if impl == "flash" and blockwise_ok else "dense")
        dropped = rate > 0.0 and (path == "dense" or rng is not None)
        obs.inc(IMPL_SELECTED, impl=path, dropout="on" if dropped else "off")

        if path == "ring":
            # Sequence stays sharded: Q/K/V keep the "seq" axis on sp and
            # K/V blocks rotate around the ring. Attention-prob dropout is
            # skipped under ring (standard for blockwise kernels).
            from ..ops.ring_attention import ring_attention

            q = split_heads(proj("query")(q_input), "seq")
            k = split_heads(proj("key")(kv_input), "seq")
            v = split_heads(proj("value")(kv_input), "seq")
            ctx = ring_attention(q, k, v, padding_mask, mesh)
        elif path == "flash":
            # The pallas fused kernel (ops/flash_attention.py). The
            # single-block kernels drop attention probabilities with the
            # Dropout_0 site's key; the online ones skip it, like ring.
            # Packed rows hand the kernel per-token segment ids — the
            # block-diagonal mask is enforced inside the kernel, no L x L
            # mask materializes.
            q = split_heads(proj("query")(q_input), None)
            k = split_heads(proj("key")(kv_input), None)
            v = split_heads(proj("value")(kv_input), None)
            dropout = dict(dropout_rate=rate if dropped else 0.0,
                           dropout_rng=rng)
            if segments is not None:
                ctx = flash_attention(q, k, v, segments=segments, **dropout)
            else:
                ctx = flash_attention(q, k, v, padding_mask, **dropout)
        else:
            # Full-sequence attention: entering this block the activations
            # all-gather from sp, and heads shard over tp.
            q = split_heads(proj("query")(q_input), None)
            k = split_heads(proj("key")(kv_input), None)
            v = split_heads(proj("value")(kv_input), None)

            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
                head_dim).astype(self.dtype)
            # Finite large-negative (not dtype-min): fp32 min overflows to
            # -inf in bf16, and an all-masked row would softmax to NaN.
            bias = 0.0
            if segments is not None:
                # Packed rows: block-diagonal — attend only same-segment,
                # non-pad keys (subsumes the padding mask).
                allowed = ((segments[:, None, :, None]
                            == segments[:, None, None, :])
                           & (segments[:, None, None, :] > 0))
                bias = jnp.where(allowed, 0.0, -1e9)
            elif padding_mask is not None:
                bias = jnp.where(padding_mask[:, None, None, :] > 0, 0.0,
                                 -1e9)
            if extra_bias is not None:
                bias = bias + extra_bias
            probs = nn.softmax(scores + jnp.asarray(bias, self.dtype),
                               axis=-1)
            probs = drop(probs, deterministic=deterministic, rng=rng)
            ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v)

        ctx = ctx.reshape(ctx.shape[0], ctx.shape[1],
                          self.num_heads * head_dim)
        # Row-parallel: input dim sharded over tp, XLA psums the output.
        out = nn.Dense(
            self.hidden_size, dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                init, ("heads", "embed")),
            name="output")(ctx)
        return with_logical(out, ("batch", "seq", "act_embed"))


class FeedForward(nn.Module):
    """Column-parallel expand (gelu) + row-parallel contract — the one
    transformer MLP both model families use (children:
    intermediate/output)."""

    hidden_size: int
    intermediate_size: int
    dtype: Any = jnp.bfloat16
    initializer_range: float = 0.02

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.normal(stddev=self.initializer_range)
        h = nn.Dense(
            self.intermediate_size, dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                init, ("embed", "mlp")),
            bias_init=nn.with_logical_partitioning(
                nn.initializers.zeros_init(), ("mlp",)),
            name="intermediate")(x)
        h = nn.gelu(h, approximate=True)
        return nn.Dense(
            self.hidden_size, dtype=self.dtype,
            kernel_init=nn.with_logical_partitioning(
                init, ("mlp", "embed")),
            name="output")(h)
