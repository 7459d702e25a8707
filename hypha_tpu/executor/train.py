"""The inner training loop: optimizer, LR schedules, losses, jitted step.

TPU-native replacement for the reference's accelerate executor hot loop
(executors/accelerate/.../training.py:106-116: zero_grad/forward/backward/
step/scheduler.step): here the whole step is ONE jit-compiled function —
forward, loss, backward, AdamW update and LR schedule fused by XLA — with
params/optimizer state sharded over the replica's mesh
(parallel.sharding) so collectives ride ICI.

LR schedules mirror the reference's Scheduler enum
(crates/messages/src/lib.rs:674-687: constant / cosine-with-warmup /
linear-with-warmup / wsd), implemented as optax schedules.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..messages import Adam, Loss, LRScheduler, LRSchedulerKind

__all__ = [
    "TrainState",
    "make_lr_schedule",
    "build_optimizer",
    "compute_loss",
    "make_loss_fn",
    "chunked_causal_ce",
    "make_train_step",
    "make_routed_train_step",
    "ROUTING_FIELDS",
    "aux_fields",
]


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    # Variable collections beside ``params`` that the step updates by a rule
    # of its own (a routed model's selection bias): not differentiated, no AdamW
    # moments, no part of the pseudo-gradient. None for every other model.
    extras: Any = None

    @classmethod
    def create(
        cls, params, tx: optax.GradientTransformation, extras: Any = None
    ) -> "TrainState":
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=tx.init(params),
            tx=tx,
            extras=extras,
        )

    def apply_gradients(self, grads) -> "TrainState":
        updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt,
        )


def make_lr_schedule(spec: LRScheduler | None, base_lr: float) -> optax.Schedule:
    if spec is None or spec.kind is LRSchedulerKind.CONSTANT:
        return optax.constant_schedule(base_lr)
    warmup = max(0, int(spec.warmup_steps))
    total = max(warmup + 1, int(spec.total_steps))
    if spec.kind is LRSchedulerKind.COSINE_WITH_WARMUP:
        return optax.warmup_cosine_decay_schedule(
            init_value=0.0,
            peak_value=base_lr,
            warmup_steps=warmup,
            decay_steps=total,
            end_value=0.0,
        )
    if spec.kind is LRSchedulerKind.LINEAR_WITH_WARMUP:
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, base_lr, warmup),
                optax.linear_schedule(base_lr, 0.0, total - warmup),
            ],
            [warmup],
        )
    if spec.kind is LRSchedulerKind.WSD:
        # warmup -> stable -> decay-to-zero; stable ends at decay_start·total
        decay_start = max(warmup, int(spec.decay_start * total))
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, base_lr, warmup),
                optax.constant_schedule(base_lr),
                optax.linear_schedule(base_lr, 0.0, max(1, total - decay_start)),
            ],
            [warmup, decay_start],
        )
    raise ValueError(f"unknown LR schedule {spec.kind}")


def build_optimizer(
    adam: Adam,
    schedule_spec: LRScheduler | None = None,
    max_grad_norm: float | None = 1.0,
    *,
    mu_dtype: Any | None = None,
) -> optax.GradientTransformation:
    """AdamW matching the reference's inner optimizer defaults
    (utils.py get_adam: betas (0.9, 0.999), eps 1e-8).

    ``mu_dtype=jnp.bfloat16`` halves the first-moment buffer — at 7B that
    is 13.5 GB off the optimizer footprint across the mesh (the second
    moment stays f32: its magnitudes span too many decades for bf16's 8
    mantissa bits; see MEM7B feasibility table).
    """
    b1, b2 = adam.betas or (0.9, 0.999)
    sched = make_lr_schedule(schedule_spec, adam.lr)
    parts = []
    if max_grad_norm is not None:
        parts.append(optax.clip_by_global_norm(max_grad_norm))
    parts.append(
        optax.adamw(
            learning_rate=sched,
            b1=b1,
            b2=b2,
            eps=adam.epsilon if adam.epsilon is not None else 1e-8,
            weight_decay=adam.weight_decay,
            mu_dtype=mu_dtype,
        )
    )
    return optax.chain(*parts)


def compute_loss(kind: Loss, logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Loss selector (crates/messages/src/lib.rs:662-670). Labels == -100 are
    ignored for classification losses (HF convention the reference relies on)."""
    if kind in (Loss.CROSS_ENTROPY, Loss.NLL):
        # CE as logsumexp − picked-logit: two streaming reductions over the
        # logits instead of materializing the full f32 log-softmax tensor —
        # at LM vocab width that tensor is gigabytes of HBM traffic per step.
        valid = labels != -100
        safe = jnp.where(valid, labels, 0)
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1
        )
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = lse - picked.astype(jnp.float32)
        return jnp.sum(nll * valid) / jnp.maximum(valid.sum(), 1)
    if kind is Loss.MSE:
        return jnp.mean((logits.astype(jnp.float32) - labels) ** 2)
    if kind is Loss.MAE:
        return jnp.mean(jnp.abs(logits.astype(jnp.float32) - labels))
    if kind is Loss.BCE_WITH_LOGITS:
        x = logits.astype(jnp.float32)
        return jnp.mean(jnp.maximum(x, 0) - x * labels + jnp.log1p(jnp.exp(-jnp.abs(x))))
    raise ValueError(f"unknown loss {kind}")


def chunked_causal_ce(
    hidden: jnp.ndarray,
    head_w: jnp.ndarray,
    labels: jnp.ndarray,
    *,
    chunk: int = 128,
) -> jnp.ndarray:
    """Streaming CE over sequence chunks — full-width logits NEVER exist.

    ``hidden`` [B, S, D] are final hidden states (already shifted by the
    caller: ``hidden[:, :-1]`` against ``labels = inputs[:, 1:]``),
    ``head_w`` [V, D] the (tied) LM head. Each ``lax.map`` iteration
    projects one sequence chunk to vocab width, reduces it to
    logsumexp − picked, and drops it; ``jax.checkpoint`` makes the
    backward recompute the chunk's logits instead of storing them. Peak
    loss memory falls from O(B·S·V) to O(B·chunk·V) — the [B,S,50257]
    f32 logits tensor is what ran GPT-2 out of device memory at B≥24.
    Labels == -100 are ignored, matching
    :func:`compute_loss` CE semantics exactly.
    """
    B, S, D = hidden.shape
    pad = (-S) % chunk
    if pad:
        # Ragged tail (the shifted caller pattern makes S odd — e.g.
        # 1023): pad with ignored positions rather than collapsing to one
        # dense chunk, which would resurrect the full logits tensor.
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-100)
        S += pad
    n = S // chunk
    hs = hidden.reshape(B, n, chunk, D).swapaxes(0, 1)  # [n, B, c, D]
    ls = labels.reshape(B, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def one(h, l):
        logits = jnp.einsum(
            "bcd,vd->bcv", h.astype(head_w.dtype), head_w,
            preferred_element_type=jnp.float32,
        )
        valid = l != -100
        safe = jnp.where(valid, l, 0)
        lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = (lse - picked.astype(jnp.float32)) * valid
        return nll.sum(), valid.sum()

    with jax.named_scope("loss"):
        sums, counts = jax.lax.map(lambda args: one(*args), (hs, ls))
        return sums.sum() / jnp.maximum(counts.sum(), 1)


def make_train_step(
    apply_fn: Callable,
    loss_kind: Loss = Loss.CROSS_ENTROPY,
    *,
    causal_lm: bool = True,
    has_aux: bool = False,
    donate: bool = True,
    dropout_seed: int | None = None,
    labels_aligned: bool = False,
    loss_override: Callable | None = None,
):
    """Build the jitted train step.

    ``apply_fn(params, batch_inputs)`` returns logits (or (logits, aux_loss)
    when ``has_aux`` — the MoE router loss). Apply functions may opt into
    richer calling conventions by declaring keyword params (inspected once
    at build time, so the jitted call stays static):

      * ``rng``   — a per-step dropout key (folded from ``dropout_seed`` and
        the step counter), enabling train-mode stochasticity; the reference
        trains its torch models in train() mode (training.py:106-116).
      * ``batch`` — the full batch dict, for models that consume extra
        streams (e.g. seq2seq ``decoder_input_ids``).

    For causal LM the labels are the *target stream* shifted left — the
    decoder stream when the batch carries one, else the inputs; otherwise
    the batch carries explicit ``labels``. ``loss_override(out, batch)``
    (a model's ``custom_loss`` — CTC, detection, contrastive, span …)
    replaces the ``compute_loss`` selector entirely.
    Returns ``step(state, batch) -> (state, metrics)``.
    """
    loss_fn = make_loss_fn(
        apply_fn,
        loss_kind,
        causal_lm=causal_lm,
        has_aux=has_aux,
        dropout_seed=dropout_seed,
        labels_aligned=labels_aligned,
        loss_override=loss_override,
    )

    def step(state: TrainState, batch) -> tuple:
        (total, (loss, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, batch, state.step
        )
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads)
        metrics = {
            "loss": loss,
            "total_loss": total,
            "aux_loss": aux,
            "grad_norm": optax.global_norm(grads),
        }
        return new_state, metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def _head_loss(model, params, hidden, ids, chunk: int):
    """The chunked causal loss of final hidden states against the leaf of
    ``params`` the model names as its head (``model.head_leaf``)."""
    head = params["params"][model.head_leaf].astype(hidden.dtype)
    return chunked_causal_ce(hidden[:, :-1], head, ids[:, 1:], chunk=chunk)


def make_chunked_train_step(model, *, loss_chunk: int = 512, donate: bool = True):
    """The jitted step of a dense causal model that names its head
    (``model.head_leaf``) and can stop before it (``with_head=False``): the
    chunked causal cross-entropy over the final hidden states, so the
    [B, S, vocab] logits never exist, through a head of its own or a tied
    embedding. ``make_train_step``'s metrics; no ``extras``."""
    body = model.clone(with_head=False)

    def loss_fn(params, ids):
        return _head_loss(model, params, body.apply(params, ids), ids, loss_chunk)

    def step(state: TrainState, batch) -> tuple:
        ids = batch["input_ids"] if "input_ids" in batch else batch["inputs"]
        loss, grads = jax.value_and_grad(loss_fn)(state.params, ids)
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads)
        metrics = {
            "loss": loss,
            "total_loss": loss,
            "aux_loss": jnp.float32(0),
            "grad_norm": optax.global_norm(grads),
        }
        return new_state, metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


# What a routed step's ``metrics["host"]`` vector holds, in order: the loss
# and the step's routing counters, so one device-to-host transfer fetches all.
ROUTING_FIELDS = (
    "loss", "pairs_routed", "pairs_computed", "load_max", "tokens_elsewhere",
    "trips", "grad_experts", "combines",
)


def aux_fields(model) -> tuple:
    """What follows ``ROUTING_FIELDS`` in the host vector of a model with a
    second objective: ``aux_loss`` (the layers' sum), then whatever per-layer
    ``stats`` the model names in ``aux_fields``, each a mean over the layers.
    Empty for a model that names no ``aux_name``."""
    if not getattr(model, "aux_name", None):
        return ()
    return ("aux_loss", *getattr(model, "aux_fields", ()))


def make_routed_train_step(model, *, loss_chunk: int = 512, donate: bool = True):
    """The jitted step of a model with routed experts and a selection bias
    (``models/routed.py``): ``model.apply`` returns ``(out, stats)`` and
    ``state.extras`` holds the ``moe_state`` collection.

    The loss is the chunked causal cross-entropy over the final hidden states
    (``with_head=False``), so the [B, S, vocab] logits never exist. The head is
    the leaf of ``params`` that the model names (``model.head_leaf``): a head
    of its own, or the embedding where the two are tied, whose gradient then
    has two sources. The bias is updated from the step's counts after the
    optimizer (``models.routed.update_bias``); it gets no gradient and no
    moments. The routing counters ride in ``metrics["host"]``
    (``ROUTING_FIELDS``) beside the loss: pairs, trips, combines and ``grad_experts``
    summed and ``load_max`` maximised over the expert layers.

    **A second objective.** Where the model's ``stats`` carry ``aux_loss`` (a
    value a layer: a learned key selection's own objective, whose gradient the
    model keeps apart from the language model's by ``stop_gradient``), the step
    differentiates the cross-entropy plus the layers' sum. The logged ``loss``
    stays the cross-entropy; ``aux_loss`` and ``total_loss`` say the rest, and
    the host vector gains :func:`aux_fields`.
    """
    from ..models.routed import STATE, update_bias

    body = model.clone(with_head=False)
    coeff = model.config.load_balance_coeff

    def loss_fn(params, extras, ids):
        hidden, stats = body.apply({**params, **extras}, ids)
        loss = _head_loss(model, params, hidden, ids, loss_chunk)
        aux = stats["aux_loss"].sum() if "aux_loss" in stats else None
        return (loss if aux is None else loss + aux), (loss, aux, stats)

    def step(state: TrainState, batch) -> tuple:
        ids = batch["input_ids"] if "input_ids" in batch else batch["inputs"]
        (total, (loss, aux, stats)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, state.extras, ids
        )
        more = [aux if k == "aux_loss" else stats[k].mean() for k in aux_fields(model)]
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads)
            new_state = new_state.replace(
                extras={STATE: update_bias(state.extras[STATE], stats["chosen"], coeff)}
            )
        counters = {  # over the expert layers: the fullest expert's load, the sum of every other
            k: stats[k].max() if k == "load_max" else stats[k].sum() for k in ROUTING_FIELDS[1:]
        }
        metrics = {
            "loss": loss,
            "total_loss": total,
            "aux_loss": jnp.float32(0) if aux is None else aux,
            "grad_norm": optax.global_norm(grads),
            "host": jnp.stack(
                [loss] + [counters[k].astype(jnp.float32) for k in ROUTING_FIELDS[1:]] + more
            ),
        }
        return new_state, metrics

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def make_loss_fn(
    apply_fn: Callable,
    loss_kind: Loss = Loss.CROSS_ENTROPY,
    *,
    causal_lm: bool = True,
    has_aux: bool = False,
    dropout_seed: int | None = None,
    labels_aligned: bool = False,
    loss_override: Callable | None = None,
) -> Callable:
    """``loss_fn(params, batch, step_no) -> (total, (loss, aux))`` with the
    full label-layout semantics documented on :func:`make_train_step`.
    Shared by the full-parameter step and the LoRA step (executor.lora), so
    the two paths can never diverge on label shifting or loss selection."""
    import inspect

    try:
        sig = set(inspect.signature(apply_fn).parameters)
    except (TypeError, ValueError):
        sig = set()
    wants_rng = "rng" in sig and dropout_seed is not None
    wants_batch = "batch" in sig

    def loss_fn(params, batch, step_no):
        inputs = batch["input_ids"] if "input_ids" in batch else batch["inputs"]
        kwargs = {}
        if wants_rng:
            kwargs["rng"] = jax.random.fold_in(jax.random.key(dropout_seed), step_no)
        if wants_batch:
            kwargs["batch"] = batch
        out = apply_fn(params, inputs, **kwargs)
        aux = jnp.float32(0)
        if has_aux:
            out, aux = out
        if loss_override is not None:
            with jax.named_scope("loss"):
                loss = loss_override(out, batch)
            return loss + aux, (loss, aux)
        if causal_lm:
            # Teacher forcing over the target stream. Three layouts:
            #   * decoder_input_ids AND labels (HF convention: decoder is
            #     labels shifted right) — out[t] already predicts labels[t],
            #     no further shift;
            #   * decoder stream only — next-token within the decoder;
            #   * otherwise — next-token over labels (== inputs by default).
            dec = batch.get("decoder_input_ids")
            explicit = batch.get("labels")
            if explicit is not None and (dec is not None or labels_aligned):
                # Decoder inputs are labels shifted right (either supplied
                # by the batch or shifted inside the model — the
                # ``labels_aligned`` seq2seq contract): out[t] predicts
                # labels[t] already.
                logits, labels = out, explicit
            elif dec is not None:
                logits, labels = out[:, :-1], dec[:, 1:]
            else:
                target = explicit if explicit is not None else inputs
                logits, labels = out[:, :-1], target[:, 1:]
        else:
            logits = out
            labels = batch["labels"]
        with jax.named_scope("loss"):
            loss = compute_loss(loss_kind, logits, labels)
        return loss + aux, (loss, aux)

    return loss_fn
