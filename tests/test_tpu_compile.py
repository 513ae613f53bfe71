"""The Pallas flash kernels and the train step compile for a described
(not attached) TPU v5e.

Interpret-mode tests cannot see what the chip's compiler refuses: slices
off the tiling, more VMEM than a kernel may use. Each flash test compiles
forward+backward at real widths (bf16, 12 heads) for one chip of a
v5e:2x2 topology and checks the program holds the kernel; the dropout
tests compile the kernels that draw the probability masks and read how
the chip's compiler fused a BERT-large step. The topology is
described inside a fixture of this one file: only one process may load
the TPU library, so describing it at import would fail on every other
test worker and change what each collects.
"""

import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lddl_tpu.ops.flash_attention import _use_onekv, pad_seq_len

# (head_dim, L, regime): the single-block regime and its limit, the
# online kernels, and the wide-head single-block bound.
SHAPES = [
    (64, 512, "single-block"),
    (64, 896, "single-block"),
    (64, 1024, "online"),
    (128, 512, "single-block"),
]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no chip
            pytest.skip("no v5e:2x2 topology can be described here: "
                        "{}".format(e))
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("head_dim,seq_len,regime", SHAPES)
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, head_dim, seq_len,
                                        regime):
    from lddl_tpu.ops.flash_attention import flash_attention

    assert _use_onekv(pad_seq_len(seq_len), head_dim) == (
        regime == "single-block")
    qkv = jax.ShapeDtypeStruct((2, seq_len, 12, head_dim), jnp.bfloat16,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((2, seq_len), jnp.int32, sharding=one_chip)

    def loss(q, k, v, m):
        out = flash_attention(q, k, v, m, interpret=False)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, mask).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # Each kernel carries its stable name into the compiled program.
    names = (("flash_fwd_onekv", "flash_bwd_onekv")
             if regime == "single-block"
             else ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkdv"))
    for name in names:
        assert name in text, name


def _top_level_fusions(hlo_text):
    """{fusion instruction: op_name of every op its called computations
    hold} for the fusions of the optimized HLO's non-fused computations."""
    bodies, cur = {}, None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            cur = re.match(r"(?:ENTRY )?%?([\w.\-]+)", line).group(1)
            bodies[cur] = []
        elif cur is not None:
            bodies[cur].append(line)
    fusion_re = re.compile(
        r"\s*(?:ROOT )?%?([\w.\-]+) = .* fusion\(.*calls=%?([\w.\-]+)")
    fusions = {}
    for comp, lines in bodies.items():
        for line in lines:
            m = fusion_re.match(line)
            if m:
                fusions[(comp, m.group(1))] = m.group(2)
    fused = set(fusions.values())

    def op_lines(comp, seen):
        if comp in seen:
            return []
        seen.add(comp)
        out = list(bodies.get(comp, ()))
        for line in bodies.get(comp, ()):
            for callee in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)",
                                     line):
                out += op_lines(callee, seen)
        return out

    return {name: op_lines(callee, set())
            for (comp, name), callee in fusions.items() if comp not in fused}


@pytest.fixture(scope="module")
def bert_large_step_hlo(one_chip):
    """Optimized HLO of the program's own train step: 2 layers at
    BERT-large widths, L=512, threefry dropout keys: attention takes the
    single-block flash kernels, which draw the probability masks, and
    XLA hashes the hidden dropout masks on the VPU."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lddl_tpu.models import BertConfig, BertForPreTraining
    from lddl_tpu.models.train import (TrainState, _init_variables,
                                       make_optimizer,
                                       make_sharded_train_step)

    cfg = BertConfig.bert_large(num_layers=2, dropout_rng_impl="threefry")
    # The backend is still the CPU: lower the kernels for the chip.
    flash_module = sys.modules["lddl_tpu.ops.flash_attention"]
    mesh = Mesh(np.array(list(one_chip.device_set)), ("dp",))
    rep = NamedSharding(mesh, P())
    batch_size, seq_len = 2, 512
    tx = make_optimizer()

    def init(rng):
        ids = jnp.zeros((batch_size, seq_len), jnp.int32)
        sample = {"input_ids": ids, "token_type_ids": ids,
                  "attention_mask": ids}
        params = nn.meta.unbox(_init_variables(
            BertForPreTraining(cfg), rng, sample))["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), tx=tx)

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32,
                                  sharding=NamedSharding(mesh, P("dp")))
    batch = {"input_ids": tokens, "token_type_ids": tokens,
             "attention_mask": tokens, "labels": tokens,
             "next_sentence_labels": jax.ShapeDtypeStruct(
                 (batch_size,), jnp.int32,
                 sharding=NamedSharding(mesh, P("dp")))}
    step = make_sharded_train_step(mesh, cfg)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(flash_module, "_interpret_default", lambda: False)
        return step.lower(state, batch, 0).compile().as_text()


@pytest.mark.parametrize("site", [
    "embeddings/Dropout_0",
    "layer_{}/attention/Dropout_0",  # attention probabilities
    "layer_{}/Dropout_0",            # attention output
    "layer_{}/Dropout_1",            # FFN output
])
def test_dropout_mask_hashed_in_one_fusion(bert_large_step_hlo, site):
    """Each hidden dropout site's threefry hash (its ``xor`` ops, found by
    their op_name metadata) sits in exactly one fusion: the backward reads
    the stored keep-mask instead of re-hashing it in each consumer. The
    attention probabilities' mask is hashed inside the flash forward
    kernel: no XLA fusion of the layer's attention scope hashes, and the
    backward kernel takes the stored mask as an operand."""
    fusions = _top_level_fusions(bert_large_step_hlo)
    for path in sorted({site.format(i) for i in range(2)}):
        if path.endswith("/attention/Dropout_0"):
            scope = "/{}/".format(path[:-len("/Dropout_0")])
            hashing = sorted(
                name for name, lines in fusions.items()
                if any(" xor(" in ln and scope in ln for ln in lines))
            assert hashing == [], (path, hashing)
            # B*H = 2*16 rows of [L_pad/32, L_pad] packed words.
            keep = "s32[32,16,512]"
            _assert_mask_passes(bert_large_step_hlo, keep, scope)
            continue
        scope = "/{}/".format(path)
        hashing = sorted(
            name for name, lines in fusions.items()
            if any(" xor(" in ln and scope in ln for ln in lines))
        assert len(hashing) == 1, (path, hashing)


def test_flash_dropout_compiles_for_v5e(one_chip):
    """The dropout kernels at BERT-large's widths and batch (B=16, H=16,
    L=512, D=64): forward and backward compile for a v5e, the forward
    returns the packed keep-mask and the backward takes it."""
    from lddl_tpu.ops.flash_attention import flash_attention

    qkv = jax.ShapeDtypeStruct((16, 512, 16, 64), jnp.bfloat16,
                               sharding=one_chip)
    mask = jax.ShapeDtypeStruct((16, 512), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def loss(q, k, v, m, key):
        out = flash_attention(q, k, v, m, interpret=False, dropout_rate=0.1,
                              dropout_rng=key)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv, mask, key).compile().as_text()
    _assert_mask_passes(text, "s32[256,16,512]")


def _assert_mask_passes(hlo_text, keep, scope=""):
    """The flash forward kernel under ``scope`` returns the packed
    keep-mask (shape ``keep``) and its backward takes it as an operand."""
    calls = {}
    for name in ("flash_fwd_onekv", "flash_bwd_onekv"):
        lines = [ln for ln in hlo_text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln
                 and scope + name in ln]
        assert len(lines) == 1, (name, scope, len(lines))
        calls[name] = lines[0]
    outputs = calls["flash_fwd_onekv"].split(" custom-call(")[0]
    operands = calls["flash_bwd_onekv"].split(
        "operand_layout_constraints={")[1].split("}, ")
    assert keep in outputs, outputs
    assert any(op.startswith(keep) for op in operands), operands
