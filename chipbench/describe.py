"""Compile a cell's train-step programs for a described TPU v5e, with no
chip attached, and print each program's memory analysis.

This is how the cells' batches were fixed: the largest batch whose step
program fits the chip's 16 GB with room to spare. Nothing runs, so it
says nothing about time. Run it on a host without a chip:

    JAX_PLATFORMS=cpu python chipbench/describe.py <workload> [--batch N]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import cell  # noqa: E402


def described_step_memory(workload, batch=None, seq_lens=None):
    """{seq_len: memory-analysis dict} of the cell's step programs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lddl_tpu.models import make_sharded_train_step

    spec = cell.load(workload)
    batch = batch or spec.traffic["loader"]["batch_size"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]), ("dp",))
    # Code that asks jax.default_backend() sees the CPU here: steer the
    # kernel's interpret switch to what the chip would take.
    fa = sys.modules.get("lddl_tpu.ops.flash_attention")
    if fa is None:
        import importlib
        fa = importlib.import_module("lddl_tpu.ops.flash_attention")
    fa._interpret_default = lambda: False

    rep = NamedSharding(mesh, P())
    abstract = jax.eval_shape(lambda k: spec.make_state(k), jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        abstract)
    step = make_sharded_train_step(mesh, spec.bert_config())
    out = {}
    for L in seq_lens or spec.seq_lens():
        b = jax.ShapeDtypeStruct((batch, L), jnp.int32,
                                 sharding=NamedSharding(mesh, P("dp", None)))
        nsp = jax.ShapeDtypeStruct((batch,), jnp.int32,
                                   sharding=NamedSharding(mesh, P("dp")))
        batch_spec = {"input_ids": b, "token_type_ids": b,
                      "attention_mask": b, "next_sentence_labels": nsp,
                      "labels": b}
        compiled = step.lower(state, batch_spec, 0).compile()
        m = compiled.memory_analysis()
        out[L] = {
            "argument_bytes": int(m.argument_size_in_bytes),
            "output_bytes": int(m.output_size_in_bytes),
            "temp_bytes": int(m.temp_size_in_bytes),
            "alias_bytes": int(m.alias_size_in_bytes),
            "peak_bytes": int(m.argument_size_in_bytes
                              + m.output_size_in_bytes
                              + m.temp_size_in_bytes
                              - m.alias_size_in_bytes),
            "flash_kernel": "tpu_custom_call" in compiled.as_text(),
        }
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("workload")
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--seq-len", type=int, nargs="*", default=None)
    args = p.parse_args(argv)
    print(json.dumps(described_step_memory(args.workload, args.batch,
                                           args.seq_len), indent=1))


if __name__ == "__main__":
    main()
