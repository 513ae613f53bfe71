"""The Pallas flash kernels compile for a described (not attached) TPU v5e.

Interpret-mode tests cannot see what the chip's compiler refuses: slices
off the tiling, more VMEM than a kernel may use. Each test here compiles
flash forward+backward at real widths (bf16, 12 heads) for one chip of a
v5e:2x2 topology and checks the program holds the kernel. The topology is
described inside a fixture of this one file: only one process may load
the TPU library, so describing it at import would fail on every other
test worker and change what each collects.
"""

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
