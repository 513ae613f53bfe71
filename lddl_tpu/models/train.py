"""Sharded pretraining step: loss, optimizer, pjit wiring.

The full consumer of the loader contract: batches (from
lddl_tpu.loader.to_device_batch) -> jitted forward/backward on an arbitrary
mesh, with params/opt-state sharded by the model's logical axis rules and
the batch sharded over the data axes. All collectives are XLA-inserted
(psum for row-parallel matmuls and the data-parallel grad reduction,
all-gather around the sequence-sharded regions).
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import optax
import flax.linen as nn
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import observability as obs
from .bert import BertForPreTraining, axis_rules_for

# Host dispatch of the train step, counted while telemetry is armed.
DISPATCH_SECONDS = "train_step_dispatch_seconds_total"
DISPATCHES = "train_step_dispatches_total"


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: dict
    opt_state: optax.OptState
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads):
        updates, new_opt_state = self.tx.update(grads, self.opt_state,
                                                self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt_state,
        )


def pretrain_loss(mlm_logits, nsp_logits, labels, next_sentence_labels,
                  ignore_index=-1):
    """Masked-LM cross entropy (mean over masked positions) + NSP cross
    entropy. NSP labels may be [B] (one sample per row) or, for packed
    rows, [B, P] with ``ignore_index`` padding unused pack slots — the
    mean then runs over real samples only. Returns (loss, metrics)."""
    mask = labels != ignore_index
    safe_labels = jnp.where(mask, labels, 0)
    mlm_ll = optax.softmax_cross_entropy_with_integer_labels(
        mlm_logits, safe_labels)
    denom = jnp.maximum(mask.sum(), 1)
    mlm_loss = jnp.where(mask, mlm_ll, 0.0).sum() / denom
    nsp_mask = next_sentence_labels != ignore_index
    nsp_safe = jnp.where(nsp_mask, next_sentence_labels, 0)
    nsp_ll = optax.softmax_cross_entropy_with_integer_labels(
        nsp_logits, nsp_safe)
    nsp_denom = jnp.maximum(nsp_mask.sum(), 1)
    nsp_loss = jnp.where(nsp_mask, nsp_ll, 0.0).sum() / nsp_denom
    loss = mlm_loss + nsp_loss
    mlm_correct = jnp.where(
        mask, jnp.argmax(mlm_logits, axis=-1) == safe_labels, False)
    nsp_correct = jnp.where(
        nsp_mask, jnp.argmax(nsp_logits, -1) == nsp_safe, False)
    metrics = {
        "loss": loss,
        "mlm_loss": mlm_loss,
        "nsp_loss": nsp_loss,
        "mlm_accuracy": mlm_correct.sum() / denom,
        "nsp_accuracy": nsp_correct.sum() / nsp_denom,
    }
    return loss, metrics


def make_optimizer(learning_rate=1e-4, weight_decay=0.01, warmup_steps=100,
                   total_steps=10000, b1=0.9, b2=0.999, clip_norm=1.0,
                   mu_dtype=None):
    """AdamW with warmup-cosine schedule and global-norm clipping.

    ``mu_dtype`` (e.g. ``jnp.bfloat16``) stores the first adam moment in
    a reduced dtype. This is a MEMORY option, not a speed option: it
    halves mu's bytes at rest, but the on-chip A/B (STEP_PROFILE.json
    ``mu_bf16_ab_step_ms``) measured it ~1.3 ms/step SLOWER on bert_large
    — XLA's convert ops cost more than the HBM traffic they save. Default
    None keeps fp32: identical update numerics to rounds 1-4 and the
    faster step (the variance nu always stays fp32)."""
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(clip_norm),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay,
                    mu_dtype=mu_dtype),
    )


def _batch_inputs(model, batch):
    """Positional model inputs drawn from the batch dict. Models declare
    their consumed keys via BATCH_INPUTS (BERT's triple by default)."""
    keys = getattr(model, "BATCH_INPUTS",
                   ("input_ids", "token_type_ids", "attention_mask"))
    return tuple(batch[k] for k in keys)


def _init_variables(model, rng, sample_batch):
    return model.init({"params": rng}, *_batch_inputs(model, sample_batch),
                      deterministic=True)


def param_shardings_of(mesh, model, sample_batch, abstract_variables=None):
    """NamedShardings for the (unboxed) param pytree, derived from the
    model's logical axis annotations + the mesh-filtered axis rules."""
    if abstract_variables is None:
        abstract_variables = jax.eval_shape(
            lambda rng: _init_variables(model, rng, sample_batch),
            jax.random.PRNGKey(0))
    logical_specs = nn.get_partition_spec(abstract_variables)["params"]
    return nn.logical_to_mesh_sharding(logical_specs, mesh,
                                       axis_rules_for(mesh))


def _mirror_param_shardings(opt_state, param_treedef, param_shardings,
                            replicated):
    """Opt-state subtrees structured like the param tree (adam mu/nu) get
    the param shardings; everything else replicates."""
    def matches(node):
        try:
            return jax.tree.structure(node) == param_treedef
        except Exception:
            return False

    if matches(opt_state):
        return param_shardings
    if hasattr(opt_state, "_fields"):  # namedtuple optax state
        return type(opt_state)(*[
            _mirror_param_shardings(getattr(opt_state, f), param_treedef,
                                    param_shardings, replicated)
            for f in opt_state._fields
        ])
    if isinstance(opt_state, (tuple, list)):
        return type(opt_state)(
            _mirror_param_shardings(s, param_treedef, param_shardings,
                                    replicated) for s in opt_state)
    return jax.tree.map(lambda _: replicated, opt_state)


def create_train_state(config, mesh, sample_batch, seed=0, optimizer=None,
                       model=None):
    """Initialize a sharded TrainState on ``mesh``.

    Params materialize directly as shards (init runs under jit with the
    target shardings), so models bigger than one device's memory
    initialize fine. Returns (state, state_shardings).
    """
    model = model or BertForPreTraining(config)
    tx = optimizer or make_optimizer()

    def init_fn(rng):
        variables = _init_variables(model, rng, sample_batch)
        params = nn.meta.unbox(variables)["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            tx=tx,
        )

    # One abstract trace serves both the param shardings and the opt-state
    # structure (tracing a large model twice costs seconds of startup).
    abstract_vars = jax.eval_shape(
        lambda rng: _init_variables(model, rng, sample_batch),
        jax.random.PRNGKey(seed))
    param_shardings = param_shardings_of(mesh, model, sample_batch,
                                         abstract_variables=abstract_vars)
    abstract_params = nn.meta.unbox(abstract_vars)["params"]
    abstract_opt = jax.eval_shape(tx.init, abstract_params)
    replicated = NamedSharding(mesh, P())
    shardings = TrainState(
        step=replicated,
        params=param_shardings,
        opt_state=_mirror_param_shardings(
            abstract_opt, jax.tree.structure(abstract_params),
            param_shardings, replicated),
        tx=tx,
    )
    with jax.set_mesh(mesh), nn.logical_axis_rules(
            axis_rules_for(mesh)):
        state = jax.jit(init_fn, out_shardings=shardings)(
            jax.random.PRNGKey(seed))
    return state, shardings


def bert_batch_loss(outputs, batch, ignore_index=-1):
    """Default loss adapter: BertForPreTraining outputs -> pretrain_loss."""
    mlm_logits, nsp_logits = outputs
    return pretrain_loss(mlm_logits, nsp_logits, batch["labels"],
                         batch["next_sentence_labels"],
                         ignore_index=ignore_index)


def _resolve_batch_loss(batch_loss, ignore_index):
    if batch_loss is not None and ignore_index != -1:
        raise ValueError(
            "ignore_index only configures the default BERT loss; bind it "
            "into your batch_loss instead")
    return batch_loss or functools.partial(bert_batch_loss,
                                           ignore_index=ignore_index)


def mlm_gather_cap(seq_len, n_samples_per_row=1):
    """Static cap P on masked positions per row for the gathered MLM head:
    the masking budget (15%) plus a 4-sigma binomial margin (dynamic
    masking draws ~Binomial(L, 0.15) per sample, uncapped), rounded up to
    a multiple of 8 for layout friendliness. Rows that exceed P (p < 1e-4
    at 4 sigma) drop the excess labels — counted in the step metrics as
    ``mlm_dropped_labels``, never silent."""
    import math
    l_eff = seq_len / max(n_samples_per_row, 1)
    per_sample = 0.15 * l_eff + 1.43 * math.sqrt(l_eff)
    p = int(math.ceil(per_sample)) * max(n_samples_per_row, 1)
    return min(seq_len, -(-p // 8) * 8)


def _dropout_key(model, seed):
    """Per-step dropout base key honoring cfg.dropout_rng_impl. "threefry"
    means jax's default threefry2x32 (via PRNGKey, so the name in the
    config stays version-stable); anything else is passed to
    jax.random.key(impl=...) verbatim (e.g. "rbg")."""
    impl = getattr(getattr(model, "cfg", None), "dropout_rng_impl", None)
    if impl is None or impl == "threefry":
        return jax.random.PRNGKey(seed)
    return jax.random.key(seed, impl=impl)


def _mlm_gather_prologue(model, batch, ignore_index, enabled):
    """Shared train/eval gather step: returns (model_kwargs, batch,
    extra_metrics) — with the gathered MLM head engaged, batch["labels"]
    is replaced by the gathered [B, P] labels and the dropped-label count
    is reported. A no-op (({}, batch, {})) when disabled or not
    applicable."""
    gather = _mlm_gather_of(model, batch, ignore_index) if enabled else None
    if gather is None:
        return {}, batch, {}
    pos, gathered_labels, dropped = gather
    return ({"masked_positions": pos}, dict(batch, labels=gathered_labels),
            {"mlm_dropped_labels": dropped})


def _mlm_gather_of(model, batch, ignore_index=-1):
    """(masked_positions [B,P], gathered labels [B,P], dropped count) when
    the model opts into the gathered MLM head, else None. Positions are
    the first P masked columns per row (ascending; rows with fewer than P
    pad with unmasked columns whose labels are already ignore_index)."""
    cfg = getattr(model, "cfg", None)
    if not getattr(cfg, "mlm_gather", False) or "labels" not in batch:
        return None
    labels = batch["labels"]
    seq_len = labels.shape[-1]
    n_per_row = 1
    if "cls_positions" in batch:  # packed rows: several samples per row
        n_per_row = batch["cls_positions"].shape[-1]
    p = mlm_gather_cap(seq_len, n_per_row)
    if p >= seq_len:
        return None  # gather would not shrink anything
    mask = labels != ignore_index
    # Strictly-decreasing positive scores at masked columns, 0 elsewhere:
    # top_k then yields the first P masked positions in ascending order.
    score = jnp.where(mask, seq_len - jnp.arange(seq_len)[None, :], 0)
    _, pos = jax.lax.top_k(score, p)
    gathered = jnp.take_along_axis(labels, pos, axis=1)
    dropped = mask.sum() - (gathered != ignore_index).sum()
    return pos, gathered, dropped


def _make_step_fn(model, batch_loss, ignore_index=-1, mlm_gather_ok=True):
    """The un-jitted SPMD step body shared by the single- and multi-step
    entry points: (state, batch, seed) -> (state, metrics).

    ``mlm_gather_ok=False`` disables the gathered MLM head: the gather
    rewrites batch["labels"] under the DEFAULT BERT loss's conventions
    (labels are [B, L] MLM ids, ignore_index marks unmasked), so a
    custom batch_loss with its own label semantics must see the original
    batch and full-sequence logits."""

    def step_fn(state, batch, seed):
        dropout_rng = jax.random.fold_in(_dropout_key(model, seed),
                                         state.step)
        kwargs, batch, extra = _mlm_gather_prologue(
            model, batch, ignore_index, mlm_gather_ok)

        def loss_fn(params):
            outputs = model.apply(
                {"params": params},
                *_batch_inputs(model, batch),
                deterministic=False,
                rngs={"dropout": dropout_rng},
                **kwargs,
            )
            with jax.named_scope("loss"):
                return batch_loss(outputs, batch)

        (_, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        # Named scopes give the device trace's ops an owner; the forward
        # and backward passes carry flax's module scopes already.
        with jax.named_scope("optimizer"):
            metrics = dict(metrics, grad_norm=optax.global_norm(grads))
            new_state = state.apply_gradients(grads)
        if extra:
            metrics = dict(metrics, **extra)
        return new_state, metrics

    return step_fn


def make_sharded_train_step(mesh, config, model=None, ignore_index=-1,
                            donate=True, batch_loss=None):
    """A jitted SPMD train step: (state, batch, seed) -> (state, metrics).

    Batch arrays must be globally-sharded jax.Arrays over the mesh's data
    axes (use lddl_tpu.loader.to_device_batch). Dropout randomness is
    deterministic per (seed, step). ``batch_loss(outputs, batch)`` ->
    (loss, metrics) adapts non-BERT models (e.g. models.bart; bind its
    ignore_index yourself, e.g. functools.partial(bart_batch_loss,
    ignore_index=...)).

    With telemetry armed, each call is a ``train_step`` span (a
    ``StepTraceAnnotation`` numbered by this step's own dispatch count)
    and adds its host dispatch time to ``train_step_dispatch_seconds_total``
    and one to ``train_step_dispatches_total``."""
    model = model or BertForPreTraining(config)
    step_fn = _make_step_fn(model,
                            _resolve_batch_loss(batch_loss, ignore_index),
                            ignore_index, mlm_gather_ok=batch_loss is None)

    jitted = jax.jit(step_fn, donate_argnums=(0,) if donate else ())
    dispatches = itertools.count()

    def dispatch(state, batch, seed):
        # Both contexts must be live at trace time: axis_rules resolves the
        # logical constraints, set_mesh resolves bare PartitionSpecs.
        with jax.set_mesh(mesh), nn.logical_axis_rules(
                axis_rules_for(mesh)):
            return jitted(state, batch, seed)

    def wrapped(state, batch, seed=0):
        if not obs.enabled():
            return dispatch(state, batch, seed)
        with obs.step_span("train_step", next(dispatches)) as span:
            out = dispatch(state, batch, seed)
        reg = obs.registry()
        reg.counter(DISPATCH_SECONDS).inc(span.seconds)
        reg.counter(DISPATCHES).inc()
        return out

    def lower(state, batch, seed=0):
        """``jax.stages.Lowered`` of the step, for callers that inspect
        or time the compiled program (``.compile()``, ``.as_text()``)."""
        with jax.set_mesh(mesh), nn.logical_axis_rules(
                axis_rules_for(mesh)):
            return jitted.lower(state, batch, seed)

    wrapped.lower = lower
    return wrapped


def make_sharded_multi_step(mesh, config, n_steps, model=None,
                            ignore_index=-1, donate=True, batch_loss=None):
    """``n_steps`` train steps in ONE dispatch: ``lax.scan`` over the step
    body — (state, batches, seed) -> (state, stacked metrics).

    The idiomatic TPU training-loop shape: one XLA computation covers many
    optimizer steps, so per-dispatch host latency (python, runtime
    dispatch) is paid once per ``n_steps``
    instead of per step, and the compiler can overlap step boundaries.

    ``batches`` leaves carry a leading ``[n_steps, ...]`` axis; each scan
    iteration consumes one slice (use lddl_tpu.loader.to_device_step_batches,
    or stack one batch n_steps times to re-feed it). Dropout still varies
    per step: the seed is folded with ``state.step``, which increments
    inside the scan."""
    model = model or BertForPreTraining(config)
    step_fn = _make_step_fn(model,
                            _resolve_batch_loss(batch_loss, ignore_index),
                            ignore_index, mlm_gather_ok=batch_loss is None)

    def multi_step_fn(state, batches, seed):
        def body(state, batch):
            return step_fn(state, batch, seed)

        return jax.lax.scan(body, state, batches, length=n_steps)

    jitted = jax.jit(multi_step_fn, donate_argnums=(0,) if donate else ())

    def wrapped(state, batches, seed=0):
        with jax.set_mesh(mesh), nn.logical_axis_rules(
                axis_rules_for(mesh)):
            return jitted(state, batches, seed)

    return wrapped


def make_eval_step(mesh, config, model=None, ignore_index=-1,
                   batch_loss=None):
    """Jitted forward-only step returning metrics."""
    model = model or BertForPreTraining(config)
    if batch_loss is not None and ignore_index != -1:
        raise ValueError(
            "ignore_index only configures the default BERT loss; bind it "
            "into your batch_loss instead")
    mlm_gather_ok = batch_loss is None  # default-loss conventions only
    batch_loss = batch_loss or functools.partial(bert_batch_loss,
                                                 ignore_index=ignore_index)

    def step_fn(params, batch):
        kwargs, batch, extra = _mlm_gather_prologue(
            model, batch, ignore_index, mlm_gather_ok)
        outputs = model.apply(
            {"params": params},
            *_batch_inputs(model, batch),
            deterministic=True,
            **kwargs,
        )
        _, metrics = batch_loss(outputs, batch)
        if extra:
            metrics = dict(metrics, **extra)
        return metrics

    jitted = jax.jit(step_fn)
    warned = [False]

    def wrapped(params, batch):
        with jax.set_mesh(mesh), nn.logical_axis_rules(
                axis_rules_for(mesh)):
            metrics = jitted(params, batch)
        # Train steps meter mlm_dropped_labels and tolerate the 4-sigma
        # cap; EVAL numbers are quoted as exact, so a capped row must be
        # loud (ADVICE r4). The host read costs one tiny-scalar sync per
        # eval step — eval callers read the metrics anyway.
        if not warned[0] and "mlm_dropped_labels" in metrics:
            if int(metrics["mlm_dropped_labels"]) > 0:
                warned[0] = True
                import warnings
                warnings.warn(
                    "mlm_gather dropped masked labels in an eval step: the "
                    "reported loss excludes them. Labels exceeded the "
                    "4-sigma cap (mlm_gather_cap); evaluate with "
                    "config.mlm_gather=False for exact loss.",
                    RuntimeWarning, stacklevel=2)
        return metrics

    return wrapped
