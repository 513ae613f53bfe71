"""Model + sharded train step: shapes, learning, sharding, mesh portability."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


from lddl_tpu.loader import to_device_batch
from lddl_tpu.models import (
    BertConfig,
    BertForPreTraining,
    create_train_state,
    make_sharded_train_step,
)
from lddl_tpu.models.train import make_eval_step, make_optimizer
from lddl_tpu.parallel import make_mesh


from lddl_tpu.models.testing import fake_pretrain_batch


def _fake_batch(cfg, B=8, L=32, seed=0):
    return fake_pretrain_batch(cfg.vocab_size, B, L, seed=seed)


@pytest.fixture(scope="module")
def tiny_cfg():
    return BertConfig.tiny()


def test_forward_shapes(tiny_cfg):
    model = BertForPreTraining(tiny_cfg)
    b = _fake_batch(tiny_cfg, B=2, L=16)
    variables = model.init(jax.random.PRNGKey(0), b["input_ids"],
                           b["token_type_ids"], b["attention_mask"])
    import flax.linen as nn
    mlm, nsp = model.apply(
        {"params": nn.meta.unbox(variables)["params"]},
        b["input_ids"], b["token_type_ids"], b["attention_mask"])
    assert mlm.shape == (2, 16, tiny_cfg.vocab_size)
    assert nsp.shape == (2, 2)
    assert mlm.dtype == np.float32


def test_param_shardings_on_mesh(tiny_cfg):
    mesh = make_mesh({"dp": 2, "tp": 4})
    batch = _fake_batch(tiny_cfg)
    state, shardings = create_train_state(tiny_cfg, mesh, batch)
    p = state.params
    # Column-parallel QKV/MLP shard their output dim over tp.
    assert p["layer_0"]["attention"]["query"]["kernel"].sharding.spec[-1] == "tp"
    assert p["layer_0"]["ffn"]["intermediate"]["kernel"].sharding.spec[-1] == "tp"
    # Row-parallel outputs shard their input dim.
    assert p["layer_0"]["attention"]["output"]["kernel"].sharding.spec[0] == "tp"
    assert p["layer_0"]["ffn"]["output"]["kernel"].sharding.spec[0] == "tp"
    # Vocab-sharded decoder (Megatron column-parallel logits); the
    # embedding TABLE rows ride fsdp only (absent on this mesh →
    # replicated) so the token gather partitions over the sharded ids
    # instead of embed-sharding its output (VERDICT r4 #2).
    emb_spec = p["embeddings"]["word_embeddings"]["embedding"].sharding.spec
    assert "tp" not in emb_spec, emb_spec
    assert p["mlm_decoder"]["kernel"].sharding.spec[-1] == "tp"
    # Adam mu mirrors param shardings.
    mu = state.opt_state[1][0].mu
    assert mu["layer_0"]["ffn"]["intermediate"]["kernel"].sharding.spec[-1] == "tp"


@pytest.mark.slow  # ~27s: full compile+train on CPU devices, budget-gated from tier-1
def test_no_full_vocab_table_all_gather_per_step(tiny_cfg):
    """The compiled fsdp×tp×sp train step must not all-gather the full
    [vocab, hidden] embedding table (VERDICT r4 #2: "vocab"→tp on the
    table made every step replicate it, and the embed-sharded gather
    output forced XLA into involuntary full rematerialization). With
    table rows on fsdp, the token gather partitions over the sharded
    ids; the largest gathers left are per-layer fsdp weight gathers."""
    import re
    import flax.linen as nn
    from lddl_tpu.models.bert import axis_rules_for
    from lddl_tpu.models import train as T

    mesh = make_mesh({"dp": 1, "fsdp": 2, "tp": 2, "sp": 2})
    batch_np = _fake_batch(tiny_cfg, B=4, L=32)
    state, _ = create_train_state(tiny_cfg, mesh, batch_np)
    model = BertForPreTraining(tiny_cfg)
    step_fn = T._make_step_fn(model, T._resolve_batch_loss(None, -1), -1,
                              True)
    batch = to_device_batch(batch_np, mesh)
    with jax.set_mesh(mesh), nn.logical_axis_rules(axis_rules_for(mesh)):
        hlo = jax.jit(step_fn).lower(state, batch, 0).compile().as_text()
    # Match sync AND async forms: "= bf16[...] all-gather(" and
    # "= (bf16[...], bf16[...]) all-gather-start(" — the full-table shape
    # must appear on the RESULT side (between '=' and the opcode), which
    # also holds on XLA printers that omit the '%' name prefix.
    table = re.escape("{},{}]".format(tiny_cfg.vocab_size,
                                      tiny_cfg.hidden_size))
    pat = re.compile(r"= \(?[^=]*" + table + r"[^=]* all-gather(-start)?\(")
    offenders = [line.strip()[:120] for line in hlo.splitlines()
                 if pat.search(line)]
    assert not offenders, offenders


@pytest.mark.slow  # ~52s: full compile+train on CPU devices, budget-gated from tier-1
def test_train_step_learns(tiny_cfg):
    """Overfit one fixed batch: loss must drop by well over chance noise."""
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    batch_np = _fake_batch(tiny_cfg, B=8, L=32)
    opt = make_optimizer(learning_rate=3e-3, warmup_steps=5, total_steps=60)
    state, _ = create_train_state(tiny_cfg, mesh, batch_np, optimizer=opt)
    step = make_sharded_train_step(mesh, tiny_cfg)
    batch = to_device_batch(batch_np, mesh)
    first = None
    for i in range(60):
        state, metrics = step(state, batch, seed=3)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first - 2.0, (first, last)
    assert int(state.step) == 60


def test_blockwise_attention_dropout_warns():
    """ring/flash skip attention-prob dropout; configuring both must warn
    (silent model drift otherwise), and dropout 0 must stay silent."""
    import warnings
    for impl in ("ring", "flash"):
        with pytest.warns(UserWarning, match="skips attention-probability"):
            BertConfig.tiny(attention_impl=impl, attention_dropout=0.1)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            BertConfig.tiny(attention_impl=impl, attention_dropout=0.0)


@pytest.mark.slow  # ~80s: full compile+train on CPU devices, budget-gated from tier-1
def test_multi_step_matches_single_steps(tiny_cfg):
    """make_sharded_multi_step(N) over stacked batches is bit-equivalent to
    N sequential single steps with the same seed (the scanned body folds
    the seed with state.step exactly like the single-step path)."""
    from lddl_tpu.loader import to_device_step_batches
    from lddl_tpu.models import make_sharded_multi_step

    mesh = make_mesh({"dp": 2, "fsdp": 2, "sp": 2})
    n = 4
    batches_np = [_fake_batch(tiny_cfg, B=8, L=32, seed=100 + i)
                  for i in range(n)]
    opt = make_optimizer(warmup_steps=2, total_steps=20)

    state, _ = create_train_state(tiny_cfg, mesh, batches_np[0],
                                  optimizer=opt)
    step = make_sharded_train_step(mesh, tiny_cfg, donate=False)
    single_losses = []
    for b in batches_np:
        state, metrics = step(state, to_device_batch(b, mesh), seed=7)
        single_losses.append(float(metrics["loss"]))
    single_params = jax.device_get(state.params)

    state2, _ = create_train_state(tiny_cfg, mesh, batches_np[0],
                                   optimizer=opt)
    multi = make_sharded_multi_step(mesh, tiny_cfg, n, donate=False)
    stacked = to_device_step_batches(
        {k: np.stack([b[k] for b in batches_np]) for k in batches_np[0]},
        mesh)
    state2, metrics = multi(state2, stacked, seed=7)
    assert int(jax.device_get(state2.step)) == n
    multi_losses = [float(x) for x in jax.device_get(metrics["loss"])]
    assert np.allclose(multi_losses, single_losses, rtol=1e-5, atol=1e-6), (
        multi_losses, single_losses)
    for a, b in zip(jax.tree.leaves(single_params),
                    jax.tree.leaves(jax.device_get(state2.params))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)


def test_attention_auto_selection(tiny_cfg):
    """attention_impl="auto" (the default) resolves by the measured rule —
    flash at L >= 256 (round-5 single-block kernels win/tie there, incl.
    the reference's L=512 headline) with attention_dropout == 0 and a
    blockwise-compatible call — and at the shortest bins produces
    bit-identical outputs to explicit dense (it IS dense there)."""
    from lddl_tpu.models.attention import resolve_auto_impl
    from lddl_tpu.models.bert import BertForPreTraining

    assert resolve_auto_impl(128, True, 0.0, head_dim=64) == "dense"
    assert resolve_auto_impl(256, True, 0.0, head_dim=64) == "flash"
    assert resolve_auto_impl(512, True, 0.0, head_dim=64) == "flash"
    # the former in-between band: single-block kernels extended to
    # l_pad <= 896 with one-row cells (1.71x kernel-level over dense at
    # L=768; 46.4 vs 38.7 MFU in-model — round-5 builder probe, not measured by the driver)
    assert resolve_auto_impl(768, True, 0.0, head_dim=64) == "flash"
    assert resolve_auto_impl(896, True, 0.0, head_dim=64) == "flash"
    assert resolve_auto_impl(1024, True, 0.0, head_dim=64) == "flash"
    # the long branch reasons in l_pad: 960 pads to 1024 (online win)
    assert resolve_auto_impl(960, True, 0.0, head_dim=64) == "flash"
    # selector mirrors the dispatcher's head-dim gate: d > 128 would
    # fall back to the (losing-at-512) online kernels, so stay dense
    assert resolve_auto_impl(512, True, 0.0, head_dim=256) == "dense"
    assert resolve_auto_impl(2048, True, 0.1, head_dim=64) == "dense"  # prob dropout
    assert resolve_auto_impl(2048, False, 0.0, head_dim=64) == "dense"  # causal/cross
    # deterministic (eval): dropout is a no-op, so flash is identical math
    # and auto may pick it even with attention_dropout > 0 (ADVICE r4).
    assert resolve_auto_impl(2048, True, 0.1, deterministic=True, head_dim=64) == "flash"
    assert resolve_auto_impl(128, True, 0.1, deterministic=True, head_dim=64) == "dense"
    assert BertConfig.tiny().attention_impl == "auto"

    batch = _fake_batch(tiny_cfg, B=4, L=64, seed=9)
    outs = {}
    for impl in ("auto", "dense"):
        cfg = BertConfig.tiny(attention_impl=impl)
        model = BertForPreTraining(cfg)
        variables = model.init(
            {"params": jax.random.PRNGKey(0)}, batch["input_ids"],
            batch["token_type_ids"], batch["attention_mask"],
            deterministic=True)
        outs[impl] = model.apply(variables, batch["input_ids"],
                                 batch["token_type_ids"],
                                 batch["attention_mask"],
                                 deterministic=True)
    np.testing.assert_array_equal(np.asarray(outs["auto"][0]),
                                  np.asarray(outs["dense"][0]))
    np.testing.assert_array_equal(np.asarray(outs["auto"][1]),
                                  np.asarray(outs["dense"][1]))


def test_attention_auto_selection_with_dropout():
    """With attention-probability dropout on, auto picks flash only where
    the single-block kernels serve (L_pad 256-896 at head_dim 64), which
    draw the dense path's own keep-mask; dense at L_pad <= 128, at L_pad
    >= 1024 (the online kernels draw no mask), for causal/cross calls, and
    under a mesh that splits the batch."""
    from lddl_tpu.models.attention import resolve_auto_impl

    assert resolve_auto_impl(128, True, 0.1, head_dim=64) == "dense"
    for seq_len in (200, 256, 512, 768, 896):
        assert resolve_auto_impl(seq_len, True, 0.1,
                                 head_dim=64) == "flash", seq_len
    for seq_len in (960, 1024, 2048):
        assert resolve_auto_impl(seq_len, True, 0.1,
                                 head_dim=64) == "dense", seq_len
    assert resolve_auto_impl(512, False, 0.1, head_dim=64) == "dense"
    devices = jax.devices()
    for axes in ({"dp": 2}, {"fsdp": 2}, {"dp": 2, "fsdp": 2, "tp": 2}):
        n = int(np.prod(list(axes.values())))
        with jax.set_mesh(make_mesh(axes, devices[:n])):
            assert resolve_auto_impl(512, True, 0.1,
                                     head_dim=64) == "dense", axes
            # without dropout there is no mask to keep whole
            assert resolve_auto_impl(512, True, 0.0,
                                     head_dim=64) == "flash", axes
    with jax.set_mesh(make_mesh({"dp": 1, "tp": 2}, devices[:2])):
        assert resolve_auto_impl(512, True, 0.1, head_dim=64) == "flash"


def _selected(model, batch, seq_len, dropout_rng=None):
    """attention_impl_selected_total's values from tracing one training
    forward of ``model`` at ``seq_len``."""
    from lddl_tpu import observability as obs
    from lddl_tpu.models.attention import IMPL_SELECTED

    b = {k: batch[k][:, :seq_len]
         for k in ("input_ids", "token_type_ids", "attention_mask")}
    obs.registry().reset()
    if dropout_rng is None:
        dropout_rng = jax.random.PRNGKey(1)
    jax.eval_shape(lambda: model.init(
        {"params": jax.random.PRNGKey(0), "dropout": dropout_rng},
        b["input_ids"], b["token_type_ids"], b["attention_mask"],
        deterministic=False))
    return obs.registry().get(IMPL_SELECTED).snapshot()["values"]


def test_auto_flash_dropout_matches_dense(monkeypatch, tmp_path):
    """A tiny BERT at L=256 with dropout on: auto (flash, the kernels
    drawing the masks) against dense under the same params and dropout
    rng, loss and gradients within bf16 tolerance. The loss reads the real
    positions' MLM logits, as training's does: the kernels hash no mask
    for padding rows, whose outputs differ from dense's. The trace-time
    counter reads flash with dropout on every layer at L=256, dense at
    L=128, and dense for an rbg key, whose mask the kernel cannot
    replay."""
    import flax.linen as nn

    seq_len = 256
    batch = fake_pretrain_batch(512, 2, seq_len, seed=4)
    batch["attention_mask"][1, 200:] = 0
    rngs = {"dropout": jax.random.PRNGKey(5)}
    results = {}
    for impl in ("auto", "dense"):
        cfg = BertConfig.tiny(attention_impl=impl,
                              max_position_embeddings=seq_len)
        model = BertForPreTraining(cfg)
        params = nn.meta.unbox(model.init(
            jax.random.PRNGKey(0), batch["input_ids"],
            batch["token_type_ids"], batch["attention_mask"],
            deterministic=True))["params"]

        def loss(p):
            mlm, nsp = model.apply(
                {"params": p}, batch["input_ids"], batch["token_type_ids"],
                batch["attention_mask"], deterministic=False, rngs=rngs)
            real = batch["attention_mask"][:, :, None] > 0
            return (jnp.where(real, jnp.square(mlm.astype(jnp.float32)),
                              0.0).mean()
                    + jnp.square(nsp.astype(jnp.float32)).mean())

        results[impl] = jax.jit(jax.value_and_grad(loss))(params)
    (la, ga), (ld, gd) = results["auto"], results["dense"]
    np.testing.assert_allclose(float(la), float(ld), rtol=1e-2)
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(gd))
    for a, d in zip(jax.tree.leaves(ga), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(d),
                                   rtol=2e-2, atol=1e-2 * scale)

    monkeypatch.setenv("LDDL_TPU_METRICS_DIR", str(tmp_path))
    model = BertForPreTraining(BertConfig.tiny(
        max_position_embeddings=seq_len))
    layers = model.cfg.num_layers
    try:
        assert _selected(model, batch, 256) == {
            "dropout=on,impl=flash": layers}
        assert _selected(model, batch, 128) == {
            "dropout=on,impl=dense": layers}
        assert _selected(model, batch, 256,
                         jax.random.key(1, impl="rbg")) == {
            "dropout=on,impl=dense": layers}
    finally:
        from lddl_tpu import observability as obs
        obs.registry().reset()


@pytest.mark.slow  # ~37s: full compile+train on CPU devices, budget-gated from tier-1
def test_mlm_gather_matches_dense_head(tiny_cfg):
    """The gathered MLM head (cfg.mlm_gather, default ON) must produce
    the same loss, metrics and updated params as the full [B, L, vocab]
    head when no row overflows the cap — unmasked logits never enter the
    loss, so gathering them away is a pure FLOP/memory cut."""
    from lddl_tpu.models.train import _mlm_gather_of, mlm_gather_cap

    mesh = make_mesh({"dp": 4, "sp": 2})
    batch_np = _fake_batch(tiny_cfg, B=8, L=64, seed=3)
    opt = make_optimizer(warmup_steps=2, total_steps=20)
    results = {}
    for gather in (True, False):
        cfg = BertConfig.tiny(mlm_gather=gather, hidden_dropout=0.0,
                              attention_dropout=0.0)
        state, _ = create_train_state(cfg, mesh, batch_np, optimizer=opt)
        step = make_sharded_train_step(mesh, cfg, donate=False)
        state, metrics = step(state, to_device_batch(batch_np, mesh), seed=7)
        results[gather] = (jax.device_get(state.params),
                           {k: float(v) for k, v in metrics.items()
                            if k != "mlm_dropped_labels"})
    assert results[True][1].keys() == results[False][1].keys()
    for k in results[False][1]:
        np.testing.assert_allclose(results[True][1][k], results[False][1][k],
                                   rtol=2e-5, atol=1e-6, err_msg=k)
    for a, b in zip(jax.tree.leaves(results[True][0]),
                    jax.tree.leaves(results[False][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-5, atol=1e-6)

    # Overflow accounting: more masked labels than the cap -> the excess
    # is dropped AND reported, never silent.
    labels = np.zeros((2, 64), np.int32)  # every column masked
    cap = mlm_gather_cap(64)
    model = __import__("lddl_tpu.models.bert", fromlist=["x"]
                       ).BertForPreTraining(BertConfig.tiny())
    got = _mlm_gather_of(model, {"labels": labels})
    assert got is not None
    pos, gathered, dropped = got
    assert pos.shape == (2, cap) and gathered.shape == (2, cap)
    assert int(dropped) == 2 * (64 - cap)


def test_mlm_gather_positions_and_logit_shape(tiny_cfg):
    """Direct model.apply with masked_positions returns [B, P, vocab] and
    matches the corresponding columns of the full head's logits."""
    model = __import__("lddl_tpu.models.bert", fromlist=["x"]
                       ).BertForPreTraining(tiny_cfg)
    batch = _fake_batch(tiny_cfg, B=4, L=32, seed=5)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["input_ids"],
        batch["token_type_ids"], batch["attention_mask"],
        deterministic=True)
    full, _ = model.apply(variables, batch["input_ids"],
                          batch["token_type_ids"], batch["attention_mask"],
                          deterministic=True)
    pos = np.stack([np.arange(8, dtype=np.int32)] * 4) * 2  # even columns
    sub, _ = model.apply(variables, batch["input_ids"],
                         batch["token_type_ids"], batch["attention_mask"],
                         deterministic=True, masked_positions=pos)
    assert sub.shape == (4, 8, tiny_cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(sub),
        np.take_along_axis(np.asarray(full), pos[:, :, None], axis=1),
        rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # ~47s: full compile+train on CPU devices, budget-gated from tier-1
def test_mesh_portability_same_loss(tiny_cfg):
    """The same seed gives the same initial loss on different meshes —
    sharding must not change the math."""
    batch_np = _fake_batch(tiny_cfg, B=8, L=16, seed=5)
    losses = []
    for axes in ({"dp": 8}, {"dp": 2, "tp": 4}, {"dp": 2, "tp": 2, "sp": 2},
                 {"dp": 2, "fsdp": 2, "tp": 2}):
        mesh = make_mesh(axes)
        state, _ = create_train_state(tiny_cfg, mesh, batch_np, seed=11)
        ev = make_eval_step(mesh, tiny_cfg)
        metrics = ev(state.params, to_device_batch(batch_np, mesh))
        losses.append(float(metrics["loss"]))
    assert np.allclose(losses, losses[0], rtol=2e-2), losses


def test_attention_mask_blocks_padding(tiny_cfg):
    """Padding positions must not influence unpadded outputs."""
    model = BertForPreTraining(tiny_cfg)
    b = _fake_batch(tiny_cfg, B=2, L=16, seed=2)
    import flax.linen as nn
    params = nn.meta.unbox(
        model.init(jax.random.PRNGKey(0), b["input_ids"],
                   b["token_type_ids"], b["attention_mask"]))["params"]
    mask = b["attention_mask"].copy()
    mask[:, 12:] = 0
    mlm1, _ = model.apply({"params": params}, b["input_ids"],
                          b["token_type_ids"], mask)
    ids2 = b["input_ids"].copy()
    ids2[:, 12:] = 1  # scramble padding content
    mlm2, _ = model.apply({"params": params}, ids2, b["token_type_ids"], mask)
    np.testing.assert_allclose(np.asarray(mlm1[:, :12]),
                               np.asarray(mlm2[:, :12]), atol=2e-2)


def _fake_bart_batch(cfg, B=4, L=24, seed=0):
    from lddl_tpu.models.testing import fake_bart_batch
    b = fake_bart_batch(cfg.vocab_size, B, L, seed=seed)
    b["attention_mask"][0, L - 5:] = 0
    b["input_ids"][0, L - 5:] = 0
    if B > 1:
        b["labels"][1, 10:] = -1  # padded targets ignored
    return b


def test_bart_forward_shapes():
    import flax.linen as nn
    from lddl_tpu.models import BartConfig, BartForPreTraining
    cfg = BartConfig.tiny()
    model = BartForPreTraining(cfg)
    b = _fake_bart_batch(cfg, B=2, L=16)
    variables = model.init(jax.random.PRNGKey(0), b["input_ids"],
                           b["attention_mask"], b["decoder_input_ids"])
    logits = model.apply({"params": nn.meta.unbox(variables)["params"]},
                         b["input_ids"], b["attention_mask"],
                         b["decoder_input_ids"])
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits)).all()


def test_bart_decoder_is_causal():
    """Changing a future decoder token must not change earlier logits."""
    import flax.linen as nn
    from lddl_tpu.models import BartConfig, BartForPreTraining
    import jax.numpy as jnp
    cfg = BartConfig.tiny(hidden_dropout=0.0, attention_dropout=0.0,
                          dtype=jnp.float32)
    model = BartForPreTraining(cfg)
    b = _fake_bart_batch(cfg, B=1, L=12)
    params = nn.meta.unbox(model.init(
        jax.random.PRNGKey(0), b["input_ids"], b["attention_mask"],
        b["decoder_input_ids"]))["params"]

    def logits_of(dec):
        return np.asarray(model.apply(
            {"params": params}, b["input_ids"], b["attention_mask"], dec,
            deterministic=True))

    base = logits_of(b["decoder_input_ids"])
    mutated = b["decoder_input_ids"].copy()
    mutated[0, 8] = (mutated[0, 8] + 1) % cfg.vocab_size
    changed = logits_of(mutated)
    np.testing.assert_allclose(base[0, :8], changed[0, :8],
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(base[0, 8:], changed[0, 8:])


@pytest.mark.slow  # ~99s: full compile+train on CPU devices, budget-gated from tier-1
def test_bart_train_step_learns():
    from lddl_tpu.models import (BartConfig, BartForPreTraining,
                                 bart_batch_loss, create_train_state,
                                 make_sharded_train_step)
    cfg = BartConfig.tiny()
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    model = BartForPreTraining(cfg)
    batch_np = _fake_bart_batch(cfg, B=4, L=32)
    state, _ = create_train_state(
        cfg, mesh, batch_np, model=model,
        optimizer=make_optimizer(learning_rate=5e-3, warmup_steps=1,
                                 total_steps=30))
    step = make_sharded_train_step(mesh, cfg, model=model,
                                   batch_loss=bart_batch_loss)
    batch = to_device_batch(batch_np, mesh)
    losses = []
    for i in range(8):
        state, metrics = step(state, batch, seed=0)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # memorizes the fixed batch


@pytest.mark.slow  # ~82s: full compile+train on CPU devices, budget-gated from tier-1
def test_bart_loader_to_model_e2e(tmp_path):
    """Full BART path: preprocess chunks -> balance -> loader -> one
    sharded train step (the consumer the reference never had)."""
    import flax.linen as nn
    from lddl_tpu.preprocess import (build_wordpiece_vocab, get_tokenizer,
                                     run_bart_preprocess)
    from lddl_tpu.balance import balance_shards
    from lddl_tpu.loader.bart import get_bart_pretrain_data_loader
    from lddl_tpu.models import (BartConfig, BartForPreTraining,
                                 bart_batch_loss, create_train_state,
                                 make_sharded_train_step)

    source = tmp_path / "corpus" / "source"
    source.mkdir(parents=True)
    words = "alpha beta gamma delta epsilon zeta eta theta".split()
    g = np.random.default_rng(0)
    with open(source / "0.txt", "w") as f:
        for d in range(30):
            sents = [" ".join(g.choice(words, 8)).capitalize() + "."
                     for _ in range(4)]
            f.write("doc-{} {}\n".format(d, " ".join(sents)))
    vocab = build_wordpiece_vocab([" ".join(words)] * 3,
                                  str(tmp_path / "v.txt"), vocab_size=120)
    tok = get_tokenizer(vocab_file=vocab)
    from lddl_tpu.preprocess.bart import BartPretrainConfig
    run_bart_preprocess({"w": str(tmp_path / "corpus")},
                        str(tmp_path / "pre"),
                        config=BartPretrainConfig(target_seq_length=48),
                        num_blocks=2, sample_ratio=1.0, seed=0)
    balance_shards(str(tmp_path / "pre"), str(tmp_path / "bal"), 2)
    loader = get_bart_pretrain_data_loader(
        str(tmp_path / "bal"), tokenizer=tok, batch_size=8,
        max_seq_length=64, fixed_seq_length=64, base_seed=3)
    batch_np = next(iter(loader))
    assert batch_np["input_ids"].shape[1] == 64

    # Pad model vocab up to a tp-divisible size (extra ids unused).
    cfg = BartConfig.tiny(vocab_size=((len(tok) + 7) // 8) * 8)
    mesh = make_mesh({"dp": 4, "tp": 2})
    model = BartForPreTraining(cfg)
    state, _ = create_train_state(cfg, mesh, batch_np, model=model,
                                  optimizer=make_optimizer(warmup_steps=1,
                                                           total_steps=5))
    step = make_sharded_train_step(mesh, cfg, model=model,
                                   batch_loss=bart_batch_loss)
    state, metrics = step(state, to_device_batch(batch_np, mesh), seed=0)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow  # ~55s: full compile+train on CPU devices, budget-gated from tier-1
def test_optimizer_mu_dtype_opt_in(tiny_cfg):
    """make_optimizer(mu_dtype=bf16) stores the first adam moment in
    bf16 (a memory-at-rest option; default stays fp32, which the on-chip
    A/B measured FASTER — STEP_PROFILE.json mu_bf16_ab_step_ms) and
    still trains."""
    import jax.numpy as jnp
    mesh = make_mesh({"dp": -1})
    batch = _fake_batch(tiny_cfg, B=8, L=32)
    for mu_dtype, expect in ((None, jnp.float32), (jnp.bfloat16,
                                                   jnp.bfloat16)):
        state, _ = create_train_state(
            tiny_cfg, mesh, batch,
            optimizer=make_optimizer(warmup_steps=1, total_steps=5,
                                     mu_dtype=mu_dtype))
        mu = state.opt_state[1][0].mu
        assert jax.tree.leaves(mu)[0].dtype == expect
        step = make_sharded_train_step(mesh, tiny_cfg)
        state, metrics = step(state, to_device_batch(batch, mesh), seed=0)
        assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow  # ~32s: full compile+train on CPU devices, budget-gated from tier-1
def test_fsdp_shards_params_and_optimizer(tiny_cfg):
    """With an fsdp mesh axis, weights and adam state live fully sharded
    (ZeRO-style): the 'embed' param dim maps to fsdp while the batch dim
    still rides (dp, fsdp)."""
    mesh = make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    batch = _fake_batch(tiny_cfg, B=8, L=32)
    state, _ = create_train_state(tiny_cfg, mesh, batch)
    p = state.params
    qkv = p["layer_0"]["attention"]["query"]["kernel"]
    assert qkv.sharding.spec[0] == "fsdp" and qkv.sharding.spec[-1] == "tp"
    # Embedding-table rows ride fsdp (embed dim replicated): the token
    # gather must come out (batch, seq)-sharded, not embed-sharded
    # (VERDICT r4 #2 — see LOGICAL_AXIS_RULES "embed_vocab").
    emb = p["embeddings"]["word_embeddings"]["embedding"]
    assert emb.sharding.spec == ("fsdp", None)
    mu = state.opt_state[1][0].mu
    assert mu["layer_0"]["attention"]["query"]["kernel"].sharding.spec[0] \
        == "fsdp"
    # The step runs and produces a finite loss.
    step = make_sharded_train_step(mesh, tiny_cfg)
    state, metrics = step(state, to_device_batch(batch, mesh), seed=0)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("family", ("bert", "bart"))
@pytest.mark.slow  # ~119s: full compile+train on CPU devices, budget-gated from tier-1
def test_remat_same_loss_and_grads(family):
    """Rematerialized layers change memory, not math: one train step with
    remat on/off from identical init produces identical loss and params."""
    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    if family == "bert":
        from lddl_tpu.models import BertConfig
        cfgs = [BertConfig.tiny(remat=r) for r in (False, True)]
        batch_np = _fake_batch(cfgs[0], B=4, L=32)
        make_kwargs = [dict() for _ in cfgs]
        models = [None, None]
    else:
        from lddl_tpu.models import (BartConfig, BartForPreTraining,
                                     bart_batch_loss)
        cfgs = [BartConfig.tiny(remat=r) for r in (False, True)]
        batch_np = _fake_bart_batch(cfgs[0], B=4, L=32)
        models = [BartForPreTraining(c) for c in cfgs]
        make_kwargs = [dict(model=m, batch_loss=bart_batch_loss)
                       for m in models]
    losses, params = [], []
    for cfg, m, kw in zip(cfgs, models, make_kwargs):
        opt = make_optimizer(warmup_steps=1, total_steps=5)
        state, _ = create_train_state(cfg, mesh, batch_np, model=m,
                                      optimizer=opt)
        step = make_sharded_train_step(mesh, cfg, **kw)
        state, metrics = step(state, to_device_batch(batch_np, mesh),
                              seed=0)
        losses.append(float(metrics["loss"]))
        params.append(jax.device_get(jax.tree.leaves(state.params)[0]))
    assert losses[0] == losses[1], losses
    np.testing.assert_array_equal(params[0], params[1])
