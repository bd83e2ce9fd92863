"""Trainer: the jit-compiled distributed training step.

This is the layer the reference's ``train.py`` scripts hand-roll (SURVEY.md
§3.3/§3.4 call stacks): forward, backward, gradient sync, AMP, gradient
accumulation, clipping, optimizer step. Here the whole step is ONE jitted
program over mesh-sharded state:

  * gradient sync     — emitted by XLA from the sharding assignment (DDP
    all-reduce / FSDP reduce-scatter+all-gather), overlapped with compute by
    the latency-hiding scheduler (the Reducer-bucket overlap story, §3.3).
  * grad accumulation — ``lax.scan`` over microbatches inside the step; the
    "no_sync" semantics of torch (skip reduction until the last microbatch)
    falls out because the psum happens once, after the scan.
  * AMP               — Policy dtypes + functional GradScaler (skip-on-inf is
    a ``jnp.where`` over the state, no host sync).
  * clipping          — global-norm over the *global* grads (sharded arrays),
    so FSDP's cross-shard ``clip_grad_norm_`` comes for free.
  * sharded update    — strategies with ``sharded_update`` (ZeRO1, FSDP)
    route the optimizer step through ``parallel.sharded_update``:
    reduce-scatter grads, step on the 1/dp shard next to the sharded
    optimizer state, all-gather params — three sharding annotations inside
    this same program (arXiv 2004.13336), so programs-per-step stays 1.
  * SyncBatchNorm     — under global-view jit, BatchNorm reduces over the
    global batch dim; XLA inserts the cross-device stat reduction. Torch's
    convert_sync_batchnorm step is unnecessary by construction.

Typical use::

    mesh = init_device_mesh((8,), ("dp",))
    trainer = Trainer(model, optax.adamw(3e-4), DataParallel(mesh),
                      loss_fn=classification_loss, policy="bf16")
    state = trainer.init(jax.random.key(0), sample_batch)
    state, metrics = trainer.step(state, batch)
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from pytorch_distributed_tpu.amp import GradScaler, Policy, get_policy
from pytorch_distributed_tpu.data.sharding import shard_batch_for_mesh
from pytorch_distributed_tpu.mesh import activation_layout
from pytorch_distributed_tpu.parallel import (
    ShardingStrategy,
    TrainState,
    make_state_shardings,
)
from pytorch_distributed_tpu.parallel import sharded_update as _zero

P = PartitionSpec

__all__ = [
    "Trainer",
    "classification_loss",
    "lm_loss",
    "lm_loss_chunked",
    "make_chunked_lm_loss",
]


# -- built-in task losses --------------------------------------------------
# signature: loss_fn(model, variables, batch, train, rngs)
#   -> (loss, (new_model_state, metrics))
# Each wraps what follows the model's forward in ``jax.named_scope("loss")``
# so the device trace can tell the loss from the model (whose ops Flax
# scopes by module name); a custom loss_fn does the same to be told apart.

def classification_loss(model, variables, batch, train: bool, rngs=None):
    """Softmax cross-entropy on ``(images, labels)`` — the ResNet configs.

    An optional third batch element is a per-example validity mask (0/1):
    padded examples (uneven final batch — the torch Join/uneven-inputs
    role, ``algorithms/join.py:104``) contribute nothing to the loss,
    metrics, or gradients; the mean divides by the REAL example count.
    Caveats: in train mode padded rows still enter BatchNorm batch
    statistics (pad with representative rows, or run the final partial
    batch in eval mode, for bit-exactness); with grad accumulation,
    microbatch means are averaged uniformly, so a padded microbatch's real
    examples weigh slightly more than others' — spread padding evenly
    across microbatches for an exact global mean."""
    if len(batch) == 3:
        x, y, mask = batch
        mask = mask.astype(jnp.float32)
    else:
        x, y = batch
        mask = None
    mutable = [k for k in variables if k != "params"]
    if train:
        if mutable:
            logits, updates = model.apply(
                variables, x, train=True, mutable=mutable, rngs=rngs
            )
            new_model_state = updates
        else:
            logits = model.apply(variables, x, train=True, rngs=rngs)
            new_model_state = {}
    else:
        logits = model.apply(variables, x, train=False)
        new_model_state = {k: v for k, v in variables.items() if k != "params"}
    with jax.named_scope("loss"):
        per_ex = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), y
        )
        hit = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        if mask is None:
            loss = per_ex.mean()
            acc = hit.mean()
        else:
            n = jnp.maximum(mask.sum(), 1.0)
            loss = (per_ex * mask).sum() / n
            acc = (hit * mask).sum() / n
    return loss, (new_model_state, {"accuracy": acc})


def _reduce_lm_loss(per_tok, mask, moe_aux, train: bool):
    """Shared tail of the LM losses: mask-aware mean, perplexity, and the
    train-only MoE router aux term."""
    if mask is None:
        loss = per_tok.mean()
    else:
        if mask.ndim == 1:
            mask = mask[:, None] * jnp.ones_like(per_tok)
        n = jnp.maximum(mask.sum(), 1.0)
        loss = (per_tok * mask).sum() / n
    metrics = {"perplexity": jnp.exp(loss)}
    if moe_aux is not None:
        # router balance term is a TRAINING objective only; eval loss
        # stays the comparable LM cross-entropy
        if train:
            loss = loss + moe_aux
        metrics["moe_aux"] = moe_aux
    return loss, ({}, metrics)


def lm_loss(model, variables, batch, train: bool, rngs=None):
    """Next-token cross-entropy on ``(tokens, targets)`` — the GPT-2
    config. Optional third element: per-example (or per-token) validity
    mask for padded uneven batches (Join/uneven-inputs role)."""
    if len(batch) == 3:
        tokens, targets, mask = batch
        mask = mask.astype(jnp.float32)
    else:
        tokens, targets = batch
        mask = None
    out = model.apply(
        variables, tokens, deterministic=not train, rngs=rngs
    )
    # MoE models return (logits, weighted router aux loss)
    logits, moe_aux = out if isinstance(out, tuple) else (out, None)
    with jax.named_scope("loss"):
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets
        )  # [B, T]
        return _reduce_lm_loss(per_tok, mask, moe_aux, train)


def make_chunked_lm_loss(n_chunks: int = 8) -> Callable:
    """LM loss via :func:`ops.chunked_xent.chunked_cross_entropy` — the
    fp32 ``[B, T, V]`` logits tensor never materializes (VERDICT r3 weak
    #2: ~3.3 GB + backward at the bench shape, the largest HBM consumer in
    the flagship GPT-2 FSDP workload).

    The model must support ``return_hidden=True`` (GPT2 / GPT2Pipe) and tie
    its head to ``params['wte']``. The head contraction runs in the
    hidden-state dtype (bf16 on TPU) with fp32 accumulation — the
    MXU-native path, vs the dense loss's fp32 einsum."""

    def lm_loss_chunked(model, variables, batch, train: bool, rngs=None):
        from pytorch_distributed_tpu.ops.chunked_xent import (
            chunked_cross_entropy,
        )

        if len(batch) == 3:
            tokens, targets, mask = batch
            mask = mask.astype(jnp.float32)
        else:
            tokens, targets = batch
            mask = None
        out = model.apply(
            variables, tokens, deterministic=not train, rngs=rngs,
            return_hidden=True,
        )
        hidden, moe_aux = out if isinstance(out, tuple) else (out, None)
        B, T, C = hidden.shape
        # the tied head lives inside the chunked loss on this path
        with jax.named_scope("loss"):
            W = variables["params"]["wte"].astype(hidden.dtype)
            per_tok = chunked_cross_entropy(
                hidden.reshape(B * T, C), W, targets.reshape(-1), n_chunks
            ).reshape(B, T)
            return _reduce_lm_loss(per_tok, mask, moe_aux, train)

    return lm_loss_chunked


#: default chunked LM loss (8 vocab chunks) — the flagship GPT-2 loss path
lm_loss_chunked = make_chunked_lm_loss()




class Trainer:
    """Builds and runs the jitted train/eval step for a sharding strategy.

    Args:
      model: flax linen module.
      optimizer: optax GradientTransformation.
      strategy: placement rules (DataParallel / FSDP / HSDP / ZeRO1 / ...).
      loss_fn: ``(model, variables, batch, train, rngs) -> (loss,
        (new_model_state, metrics))``; see classification_loss / lm_loss.
      policy: 'fp32' | 'bf16' | 'fp16' | Policy — batch-cast + scaler gating.
        (Model compute dtype is the model's own ``dtype`` attr; set both.)
      grad_accum_steps: microbatch count; batch dim must be divisible.
      scaler: GradScaler for fp16 (defaults to enabled iff policy is fp16).
      clip_norm: global-norm gradient clipping threshold.
    """

    def __init__(
        self,
        model,
        optimizer: optax.GradientTransformation,
        strategy: ShardingStrategy,
        *,
        loss_fn: Callable = classification_loss,
        policy="fp32",
        grad_accum_steps: int = 1,
        scaler: Optional[GradScaler] = None,
        clip_norm: Optional[float] = None,
    ):
        self.model = model
        self.optimizer = optimizer
        self.strategy = strategy
        self.loss_fn = loss_fn
        self.policy: Policy = get_policy(policy)
        self.grad_accum_steps = int(grad_accum_steps)
        if scaler is None and self.policy.needs_loss_scaling:
            scaler = GradScaler()
        self.scaler = scaler
        self.clip_norm = clip_norm
        self._step_fn = None
        self._eval_fn = None
        self.state_shardings: Optional[TrainState] = None

    # -- init --------------------------------------------------------------
    def init(self, rng, sample_batch, *, init_kwargs: Optional[dict] = None) -> TrainState:
        """Create the sharded TrainState. ``sample_batch`` is a host batch
        (its shapes define the model trace); params materialize directly in
        their target sharding via jit out_shardings — no host-side full
        materialization (important for FSDP-scale models)."""
        init_kwargs = dict(init_kwargs or {})
        x = sample_batch[0] if isinstance(sample_batch, tuple) else sample_batch
        x = jnp.asarray(np.asarray(x)[:1])  # single example is enough to trace

        def init_fn(rng):
            variables = self.model.init(rng, x, **init_kwargs)
            params = variables["params"]
            model_state = {k: v for k, v in variables.items() if k != "params"}
            return TrainState(
                step=jnp.int32(0),
                params=params,
                model_state=model_state,
                opt_state=self.optimizer.init(params),
                scaler=self.scaler.init() if self.scaler else None,
            )

        shapes = jax.eval_shape(init_fn, rng)
        self.state_shardings = make_state_shardings(shapes, self.strategy)
        return jax.jit(init_fn, out_shardings=self.state_shardings)(rng)

    # -- the step ----------------------------------------------------------
    def _make_step_fn(self) -> Callable:
        """The raw (unjitted) train step: ``step_fn(state, batch, rng) ->
        (new_state, metrics)``. ``_build_step`` jits it with donation +
        the pinned state layout; :class:`..pipeline_exec.AsyncRunner`
        composes it with an on-device metric ring instead, so both
        executors run the SAME program logic (the bit-exactness the
        pipelined-parity oracle in tests/test_pipeline_exec.py pins)."""
        # sequence_parallel is a layout promise the MODEL must honor via an
        # activation constraint; catch the silently-inert combination
        # (round-1 weakness: SP spec existed but nothing consumed it)
        if getattr(self.strategy, "sequence_parallel", False):
            cfg = getattr(self.model, "cfg", None)
            if cfg is not None and getattr(cfg, "act_constraint", None) is None:
                import warnings

                warnings.warn(
                    "strategy has sequence_parallel=True but the model has "
                    "no act_constraint wired — activations will NOT be "
                    "sequence-sharded. Build the model with "
                    "cfg.act_constraint=strategy.activation_constraint().",
                    stacklevel=3,
                )
        model = self.model
        loss_fn = self.loss_fn
        optimizer = self.optimizer
        scaler = self.scaler
        clip_norm = self.clip_norm
        accum = self.grad_accum_steps
        policy = self.policy
        strategy = self.strategy
        batch_spec = self.strategy.batch_pspec()
        mesh = self.strategy.mesh.jax_mesh
        # ZeRO sharded weight update (parallel/sharded_update.py): constrain
        # grads into the update layout right after they're computed, run the
        # optimizer on the 1/axis shard, gather params back — still ONE
        # program, the collectives are the partitioner's to place.
        use_sharded_update = bool(getattr(strategy, "sharded_update", False))

        def forward(params, model_state, batch, scale, rngs):
            variables = {"params": params, **model_state}
            with self._activations_pinned(params):
                loss, (new_ms, metrics) = loss_fn(
                    model, variables, batch, True, rngs
                )
            with jax.named_scope("loss"):
                scaled = loss * scale.astype(loss.dtype)
            return scaled, (loss, new_ms, metrics)

        grad_fn = jax.grad(forward, has_aux=True)

        def compute_grads(params, model_state, batch, scale, step_rng):
            """Gradient computation incl. accumulation: returns
            (grads, loss, new_model_state, metrics)."""
            if accum > 1:
                def micro(carry, xs):
                    mb, mb_idx = xs
                    g_acc, ms = carry
                    mb_rngs = {
                        "dropout": jax.random.fold_in(step_rng, mb_idx)
                    }
                    g, (loss, new_ms, metrics) = grad_fn(
                        params, ms, mb, scale, mb_rngs
                    )
                    g_acc = jtu.tree_map(jnp.add, g_acc, g)
                    return (g_acc, new_ms), (loss, metrics)

                mb_batch = jtu.tree_map(
                    lambda x: x.reshape(
                        (accum, x.shape[0] // accum) + x.shape[1:]
                    ),
                    batch,
                )
                g0 = jtu.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )
                (grads, new_model_state), (losses, metrics) = jax.lax.scan(
                    micro, (g0, model_state),
                    (mb_batch, jnp.arange(accum)),
                )
                grads = jtu.tree_map(lambda g: g / accum, grads)
                return (grads, losses.mean(), new_model_state,
                        jtu.tree_map(lambda m: m.mean(), metrics))
            grads, (loss, new_ms, metrics) = grad_fn(
                params, model_state, batch, scale,
                {"dropout": step_rng},
            )
            return grads, loss, new_ms, metrics

        def step_fn(state: TrainState, batch, rng):
            batch = jtu.tree_map(
                lambda x: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, batch_spec if x.ndim else P())
                ),
                batch,
            )
            batch = policy.cast_to_compute(batch)
            step_rng = jax.random.fold_in(rng, state.step)
            use_scaling = scaler is not None and scaler.enabled
            scale = (
                state.scaler.scale if use_scaling else jnp.float32(1.0)
            )

            grads, loss, new_model_state, metrics = compute_grads(
                state.params, state.model_state, batch, scale, step_rng
            )

            if use_sharded_update:
                # reduce-scatter point: unscale, the finite check, and
                # global-norm clipping below all run on sharded grads
                grads = _zero.shard_grads(strategy, grads)

            if use_scaling:
                grads, all_finite = scaler.unscale(grads, state.scaler)
                new_scaler = scaler.update(state.scaler, all_finite)
            else:
                all_finite = jnp.bool_(True)
                new_scaler = state.scaler

            with jax.named_scope("grad_clip"):
                grad_norm = optax.global_norm(grads)
                if clip_norm is not None:
                    factor = jnp.minimum(1.0, clip_norm / (grad_norm + 1e-6))
                    grads = jtu.tree_map(lambda g: g * factor, grads)

            with jax.named_scope("optimizer"):
                if use_sharded_update:
                    # shard-local optimizer step + all-gather of updated
                    # params
                    new_params, new_opt_state = _zero.apply_sharded_update(
                        optimizer, strategy, grads, state.opt_state,
                        state.params,
                    )
                else:
                    updates, new_opt_state = optimizer.update(
                        grads, state.opt_state, state.params
                    )
                    new_params = optax.apply_updates(state.params, updates)

            # skip-on-inf: keep old state wherever the step was non-finite
            def pick(new, old):
                return jtu.tree_map(
                    lambda n, o: jnp.where(all_finite, n, o), new, old
                )

            new_state = TrainState(
                step=state.step + 1,
                params=pick(new_params, state.params),
                model_state=new_model_state,
                opt_state=pick(new_opt_state, state.opt_state),
                scaler=new_scaler,
            )
            out_metrics = {
                "loss": loss,
                "grad_norm": grad_norm,
                "all_finite": all_finite,
                **metrics,
            }
            if use_scaling:
                out_metrics["loss_scale"] = state.scaler.scale
            return new_state, out_metrics

        return step_fn

    def _build_step(self):
        step_fn = self._make_step_fn()
        # Pin the strategy's layout on the updated state so XLA's sharding
        # propagation can never drift it (ZeRO1: grads/params are replicated,
        # so without the pin XLA could legally replicate the opt state and
        # silently defeat the sharding the strategy promises).
        out_shardings = None
        if self.state_shardings is not None:
            mesh = self.strategy.mesh.jax_mesh
            metric_sharding = NamedSharding(mesh, P())  # scalars, replicated
            out_shardings = (self.state_shardings, metric_sharding)
        return jax.jit(
            step_fn, donate_argnums=(0,), out_shardings=out_shardings
        )

    def _ensure_shardings(self, state: TrainState) -> None:
        if self.state_shardings is None:
            # state created outside init() (e.g. checkpoint restore):
            # adopt its current shardings as the pinned layout
            self.state_shardings = jtu.tree_map(
                lambda x: x.sharding, state
            )

    def _ensure_built(self, state: TrainState) -> None:
        self._ensure_shardings(state)
        if self._step_fn is None:
            self._step_fn = self._build_step()

    def step(self, state: TrainState, batch, rng=None) -> Tuple[TrainState, Dict]:
        """One optimizer step. ``batch`` may be host numpy (placed onto the
        mesh with the strategy's batch sharding) or already-placed arrays."""
        self._ensure_built(state)
        if rng is None:
            rng = jax.random.key(0)
        batch = self._place_batch(batch)
        return self._step_fn(state, batch, rng)

    def run(self, state: TrainState, batches, rng=None, *, depth: int = 2,
            drain_every: int = 32):
        """Drive a whole batch stream through the pipelined executor
        (:class:`..pipeline_exec.AsyncRunner`): up to ``depth`` steps stay
        in flight against the donated state, metrics accumulate on device
        in a ring drained by non-blocking readback every ``drain_every``
        steps, and the host blocks only at the end. Returns
        ``(final_state, MetricHistory)`` — per-step metric series,
        bit-exact with sequential :meth:`step` calls."""
        from pytorch_distributed_tpu.pipeline_exec import AsyncRunner

        runner = AsyncRunner(self, depth=depth, drain_every=drain_every)
        return runner.run(state, batches, rng=rng)

    def compile_step(self, state: TrainState, batch, rng=None):
        """Explicitly lower + compile the train step for these arguments.

        Returns ``(compiled, placed_batch, rng)`` where ``compiled`` is the
        XLA executable (``compiled(state, placed_batch, rng)`` runs the step;
        ``compiled.as_text()`` is its optimized HLO). This is the supported
        surface for inspecting the compiled step — the multi-chip dryrun
        gate's collective assertions use it instead of reaching into the
        jit internals."""
        self._ensure_built(state)
        if rng is None:
            rng = jax.random.key(0)
        placed = self._place_batch(batch)
        compiled = self._step_fn.lower(state, placed, rng).compile()
        return compiled, placed, rng

    def step_artifacts(self, state: TrainState, batch, rng=None):
        """Both IR artifacts of the train step: ``(lowered, compiled)``.

        ``lowered.as_text()`` is StableHLO (donation *intent* as
        ``tf.aliasing_output`` attrs), ``compiled.as_text()`` is the
        optimized HLO (realized ``input_output_alias`` + the
        post-partitioning collective set). This is the graftir
        (``analysis/ir``) audit surface; like :meth:`compile_step` it
        only traces — nothing executes and ``state`` is not consumed."""
        self._ensure_built(state)
        if rng is None:
            rng = jax.random.key(0)
        placed = self._place_batch(batch)
        lowered = self._step_fn.lower(state, placed, rng)
        return lowered, lowered.compile()

    # -- eval --------------------------------------------------------------
    def _build_eval(self):
        model = self.model
        loss_fn = self.loss_fn
        policy = self.policy

        def eval_fn(state: TrainState, batch):
            batch = policy.cast_to_compute(batch)
            variables = {"params": state.params, **state.model_state}
            with self._activations_pinned(state.params):
                loss, (_, metrics) = loss_fn(
                    model, variables, batch, False, None
                )
            return {"loss": loss, **metrics}

        return jax.jit(eval_fn)

    def eval_step(self, state: TrainState, batch) -> Dict:
        if self._eval_fn is None:
            self._eval_fn = self._build_eval()
        return self._eval_fn(state, self._place_batch(batch))

    # -- helpers -----------------------------------------------------------
    def _activations_pinned(self, params):
        """Context for tracing the model's forward: where the strategy
        shards parameters over an axis the batch is sharded over too (FSDP,
        HSDP), the models' ``pin_activation`` sites hold the activations to
        the batch layout, so the partitioner gathers the parameter at each
        use and never an activation. The layout is read off the mesh and
        the specs (``params``: arrays or tracers, only paths and shapes
        are read); where the strategy has none, nothing is emitted and the
        step is the program it was."""
        strategy = self.strategy
        spec = strategy.activation_pin(_zero.param_pspecs(strategy, params))
        return activation_layout(
            None if spec is None
            else NamedSharding(strategy.mesh.jax_mesh, spec)
        )

    def _place_batch(self, batch):
        leaves = jtu.tree_leaves(batch)
        if leaves and all(isinstance(x, jax.Array) for x in leaves):
            return batch
        return shard_batch_for_mesh(
            batch, self.strategy.mesh, self.strategy.batch_axes
        )
