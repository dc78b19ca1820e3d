"""Local training as a jitted scan — the TPU form of ``ClientTrainer.train``.

The reference's local loop (``ml/trainer/my_model_trainer_classification.py:21``)
is epochs x minibatches of torch fwd/bwd/step on one device.  Here the same
loop is ``lax.scan`` over ``epochs * steps_per_epoch`` steps of an optax
update, so XLA compiles ONE program per round and the whole client dimension
vmaps/shards over the mesh (SURVEY.md §3.1 "hot loops -> jit(scan)").

Ragged client shards (SURVEY.md §7 hard part 1) are handled by:
- cyclic-padded shards (every slot is a real sample, see ``data.dataset``),
- per-epoch permutations for shuffled epoch semantics,
- ``step_mode="match"``: steps beyond a client's own budget
  ``epochs * ceil(count/batch)`` (``own_step_budget``) are masked to no-ops,
  reproducing the reference's per-client step counts while keeping shapes
  static.  A masked step is not free: it runs its whole forward, backward and
  optimizer update and a ``where`` throws the result away.  With no
  ``step_bound`` the scan runs all ``epochs * steps_per_epoch`` steps, so a
  lane pays for the capacity of the largest client of the population.  With
  ``step_bound`` (an unbatched int32 the caller computes: the round engine's
  bucketed program sorts its lanes by budget and passes the longest budget
  among the lanes of a bucket, ``sim/engine.py:_run_lane_buckets``) the loop
  ends there, and only lanes shorter than their bucket's longest still
  compute masked steps.

Algorithm customisation is via two pure hooks (closed over at build time):
``loss_extra(params, global_params, ctx)`` (FedProx/FedDyn terms) and
``grad_hook(grads, ctx)`` (SCAFFOLD/Mime corrections).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

from functools import partial

import jax
import jax.numpy as jnp
import optax

from ..core import pytree as pt
from .losses import get_loss_fn
from .types import HParams


def make_optimizer(hp: HParams) -> optax.GradientTransformation:
    if hp.client_optimizer == "sgd":
        chain = []
        if hp.weight_decay:
            chain.append(optax.add_decayed_weights(hp.weight_decay))
        chain.append(optax.sgd(hp.learning_rate, momentum=hp.momentum or None))
        return optax.chain(*chain)
    if hp.client_optimizer == "adam":
        return optax.adamw(hp.learning_rate, weight_decay=hp.weight_decay)
    raise ValueError(f"unknown client optimizer {hp.client_optimizer!r}")


def own_step_budget(hp: HParams, count):
    """A client's own step count under ``step_mode="match"`` (reference:
    ``epochs * ceil(len(local) / batch)``)."""
    return hp.epochs * ((count + hp.batch_size - 1) // hp.batch_size)


def split_variables(variables: dict) -> tuple[Any, dict]:
    """Split flax variables into (params, rest-collections e.g. batch_stats)."""
    params = variables["params"]
    rest = {k: v for k, v in variables.items() if k != "params"}
    return params, rest


def make_local_train_fn(
    model,
    hp: HParams,
    loss_extra: Optional[Callable] = None,
    grad_hook: Optional[Callable] = None,
    batch_constraint: Optional[Callable] = None,
):
    """Build ``local_train(variables, x, y, count, key, ctx, step_bound) -> (new_variables, metrics)``.

    ``ctx`` is an arbitrary pytree threaded to the hooks (global params,
    control variates, server momentum...).  All shapes static; jit/vmap-safe.

    ``step_bound`` (``step_mode="match"`` only) ends the step loop after that
    many steps instead of ``epochs * steps_per_epoch``: an int32 scalar that
    must not be smaller than the client's own budget and, under ``vmap``, must
    be unbatched (one trip count for all lanes).  ``None`` is the static scan.

    ``batch_constraint(bx, by) -> (bx, by)`` is applied to each step's
    gathered minibatch — the intra-silo data-parallel hook: constraining the
    batch dim to a device axis makes GSPMD partition the fwd/bwd compute and
    insert the gradient all-reduce (without it, sharding only the at-rest
    arrays gets re-assembled by the random-index gather and the compute
    replicates).
    """
    if hp.steps_per_epoch <= 0:
        raise ValueError(
            "HParams.steps_per_epoch must be positive (got "
            f"{hp.steps_per_epoch}); build it via algorithms.hparams_from_config"
            "(cfg, steps_per_epoch=ceil(capacity/batch)) or the simulator, which"
            " computes it from the stacked client capacity"
        )
    base_loss = get_loss_fn(hp.loss)
    opt = make_optimizer(hp)
    compute_dtype = jnp.bfloat16 if hp.compute_dtype == "bfloat16" else jnp.float32

    def loss_fn(params, rest, x, y, dropout_key, ctx):
        variables = {"params": params, **rest}
        mutable = [k for k in rest.keys()]
        x = x.astype(compute_dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x
        if mutable:
            logits, new_rest = model.apply(
                variables, x, train=True, mutable=mutable, rngs={"dropout": dropout_key}
            )
        else:
            logits = model.apply(variables, x, train=True, rngs={"dropout": dropout_key})
            new_rest = rest
        loss = base_loss(logits.astype(jnp.float32), y)
        if loss_extra is not None:
            loss = loss + loss_extra(params, ctx)
        return loss, new_rest

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def local_train(variables: dict, x: jax.Array, y: jax.Array, count: jax.Array, key: jax.Array,
                    ctx=None, step_bound=None):
        params, rest = split_variables(variables)
        if step_bound is not None and hp.step_mode != "match":
            raise ValueError(
                "step_bound cuts the step loop at the longest own budget of the "
                f"lanes; step_mode={hp.step_mode!r} has no own budgets to cut at"
            )
        if x.shape[0] < hp.batch_size:
            # the old per-epoch dynamic_slice rejected this at trace time
            # (slice size > dim); keep the refusal explicit
            raise ValueError(
                f"client shard capacity {x.shape[0]} is smaller than "
                f"batch_size {hp.batch_size}; pad the shard (stack_clients "
                "with multiple_of=batch_size) or lower the batch size"
            )
        opt_state = opt.init(params)
        # A stateless optimizer (plain SGD: no momentum/adam moments) lets
        # step_mode=match masking ride a multiply on the updates instead of a
        # 2-tree select: u*active is bit-identical to the select for a 0/1
        # mask and fuses into the same FMA pass as apply_updates.
        stateless_opt = not jax.tree_util.tree_leaves(opt_state)
        cap = x.shape[0]
        bsz = hp.batch_size
        spe = hp.steps_per_epoch
        total_steps = hp.epochs * spe
        own_steps = own_step_budget(hp, count)

        # Per-epoch permutations hoisted OUT of the step scan: the permutation
        # is constant within an epoch, but recomputing it per step costs a
        # cap-sized sort per client per step (sorts are multi-pass on TPU and
        # showed up as real round time in scripts/profile_fedavg.py).  The
        # flattened (epochs*cap,) table holds epoch e's permutation at offset
        # e*cap, so each step slices its batch at epoch*cap + step*bsz.
        all_perms = jax.vmap(
            lambda e: jax.random.permutation(
                jax.random.fold_in(jax.random.fold_in(key, e), 1), cap
            )
        )(jnp.arange(hp.epochs)).reshape(-1)

        # what a step reads of its client, handed over and not closed over: the
        # bounded loop below has to say which of it is a lane's own
        env = (x, y, all_perms, own_steps, key, ctx)

        def step(env, carry, s):
            x, y, all_perms, own_steps, key, ctx = env
            params, rest, opt_state = carry
            epoch = s // spe
            step_in_epoch = s % spe
            ekey = jax.random.fold_in(key, epoch)
            # clamp the slice start inside the epoch's own block — the old
            # per-epoch dynamic_slice clamped at cap-bsz, and when cap is not
            # a batch multiple an unclamped flat offset would read into the
            # NEXT epoch's permutation (cap >= bsz is asserted above)
            start = jnp.minimum(step_in_epoch * bsz, cap - bsz)
            idx = jax.lax.dynamic_slice_in_dim(all_perms, epoch * cap + start, bsz)
            bx = jnp.take(x, idx, axis=0)
            by = jnp.take(y, idx, axis=0)
            if batch_constraint is not None:
                bx, by = batch_constraint(bx, by)
            dkey = jax.random.fold_in(ekey, 2 + step_in_epoch)
            (loss, new_rest), grads = grad_fn(params, rest, bx, by, dkey, ctx)
            if grad_hook is not None:
                grads = grad_hook(grads, ctx)
            updates, new_opt = opt.update(grads, opt_state, params)
            if hp.step_mode == "match":
                active = s < own_steps
                if stateless_opt:
                    # where(), not u*active: inf/NaN updates on inactive steps
                    # would turn 0*inf into NaN and corrupt the frozen params
                    updates = jax.tree_util.tree_map(
                        lambda u: jnp.where(active, u, jnp.zeros_like(u)), updates
                    )
                    new_params = optax.apply_updates(params, updates)
                else:
                    new_params = optax.apply_updates(params, updates)
                    new_params = _select_tree(active, new_params, params)
                    new_opt = _select_tree(active, new_opt, opt_state)
                new_rest = _select_tree(active, new_rest, rest)
                loss = jnp.where(active, loss, 0.0)
                active_f = active.astype(jnp.float32)
            else:
                new_params = optax.apply_updates(params, updates)
                active_f = jnp.float32(1.0)
            return (new_params, new_rest, new_opt), (loss, active_f)

        if step_bound is None:
            (params, rest, _), (losses, actives) = jax.lax.scan(
                partial(step, env), (params, rest, opt_state), jnp.arange(total_steps)
            )
            n_active = jnp.maximum(jnp.sum(actives), 1.0)
            loss_sum = jnp.sum(losses)
        else:
            # the same step, its loss and active count carried instead of
            # stacked: the trip count is a value of the program, not a shape
            def bounded(env, s, carry):
                state, loss_sum, n_active = carry
                state, (loss, active_f) = step(env, state, s)
                return state, loss_sum + loss, n_active + active_f

            (params, rest, _), loss_sum, n_active = _bounded_loop(
                bounded, step_bound, env,
                ((params, rest, opt_state), jnp.float32(0.0), jnp.float32(0.0)),
            )
            n_active = jnp.maximum(n_active, 1.0)
        metrics = {
            "train_loss": loss_sum / n_active,
            "num_steps": n_active,
            "num_samples": count.astype(jnp.float32),
        }
        return {"params": params, **rest}, metrics

    return local_train


def _bounded_loop(body, bound, env, init):
    """``fori_loop(0, bound, lambda s, c: body(env, s, c), init)`` with a
    ``vmap`` rule of its own: the loop stays where it is and runs the vmapped
    body, ``bound`` being one trip count for all lanes.  jax's own rule for a
    ``while`` gives the same program by re-interpreting the traced body once
    for each guess at which carries are batched and once more for the result;
    with a model's whole training step as the body that is most of the time
    the round program takes to trace (PERF.md section 6, PR 27).  Here no
    traced body is gone over again: the body is traced as it stands and, under
    a ``vmap``, once more as a vmapped function."""

    @jax.custom_batching.custom_vmap
    def loop(bound, env, carry):
        return jax.lax.fori_loop(0, bound, lambda s, c: body(env, s, c), carry)

    @loop.def_vmap
    def loop_over_lanes(axis_size, in_batched, bound, env, carry):
        bound_batched, env_batched, carry_batched = in_batched
        if bound_batched:
            raise ValueError("step_bound must be one trip count for all lanes of a vmap")
        # a carry that is not yet a lane's own becomes one inside the loop
        carry = jax.tree_util.tree_map(
            lambda a, b: a if b else jnp.broadcast_to(a, (axis_size,) + a.shape), carry, carry_batched)
        lanes_body = jax.vmap(
            body, in_axes=(jax.tree_util.tree_map(lambda b: 0 if b else None, env_batched), None, 0))
        out = jax.lax.fori_loop(0, bound, lambda s, c: lanes_body(env, s, c), carry)
        return out, jax.tree_util.tree_map(lambda _: True, carry_batched)

    return loop(bound, env, init)


def _select_tree(pred, on_true, on_false):
    return jax.tree_util.tree_map(lambda t, f: jnp.where(pred, t, f), on_true, on_false)


def make_full_grad_fn(model, hp: HParams):
    """Gradient of the mean loss over a client's whole (cyclic-padded) shard,
    at fixed variables — the FedSGD client step and Mime's ``grad f_i(x)``.
    Batched scan; batch_stats frozen (inference statistics)."""
    base_loss = get_loss_fn(hp.loss)
    bsz = hp.batch_size

    def full_grad(variables: dict, x: jax.Array, y: jax.Array, count: jax.Array, key: jax.Array):
        params, rest = split_variables(variables)
        cap = x.shape[0]
        n_batches = cap // bsz

        def loss_of(params, bx, by, dkey):
            if rest:
                logits, _ = model.apply(
                    {"params": params, **rest}, bx, train=True,
                    mutable=list(rest.keys()), rngs={"dropout": dkey},
                )
            else:
                logits = model.apply({"params": params}, bx, train=True, rngs={"dropout": dkey})
            return base_loss(logits.astype(jnp.float32), by)

        gfn = jax.grad(loss_of)

        def body(acc, i):
            bx = jax.lax.dynamic_slice_in_dim(x, i * bsz, bsz)
            by = jax.lax.dynamic_slice_in_dim(y, i * bsz, bsz)
            g = gfn(params, bx, by, jax.random.fold_in(key, i))
            return jax.tree_util.tree_map(jnp.add, acc, g), None

        zero = jax.tree_util.tree_map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        acc, _ = jax.lax.scan(body, zero, jnp.arange(n_batches))
        return jax.tree_util.tree_map(lambda g: g / jnp.maximum(n_batches, 1), acc)

    return full_grad


# Elements ONE evaluation step may take in and put out: 512 CIFAR-10 samples,
# each 32 x 32 x 3 of input and 10 logits.  Chosen on the chip (PERF.md section
# 6, PR 32): ResNet-20 over 10,000 test images takes as long as the 128-sample
# tiles its steps fill, so 313 x 32 reads 61.4 ms and 79 x 128 13.1; 20 x 504
# (12.4-12.7 ms) is the smallest batch within 5% of the best (10 x 1,000, 12.3),
# and from 1,432 up the step's activations leave fast memory (5 x 2,000: 14.6).
EVAL_STEP_ELEMENTS = 512 * (32 * 32 * 3 + 10)


def eval_batch_size(n_test: int, sample_elements: int, floor: int, lanes: int = 1) -> int:
    """Batch of the evaluation scan (``make_eval_fn``) over ``n_test`` samples
    of ``sample_elements`` each (a sample's input elements plus its logits':
    a token is small going in and a vocabulary wide coming out): the fewest
    EQUAL steps that each stay under ``EVAL_STEP_ELEMENTS``, the batch
    rounded up to a multiple of 8 so that ``pad_eval_set`` adds a few samples
    and not a step, and never under ``floor``.  ``lanes`` counts the model
    copies a vmapped evaluation scores in one step.  What ``evaluate``
    returns does not depend on its batch (running statistics, a masked sum
    over the count); its time does."""
    most = max(EVAL_STEP_ELEMENTS // (sample_elements * lanes), 1)
    steps = -(-n_test // most)
    batch = -(-n_test // steps)
    return max(-(-batch // 8) * 8, floor)


def make_eval_fn(model, hp: HParams, batch_size: int = 256):
    """Global test eval: batched scan over a (padded) test set with a
    validity mask; returns (loss, accuracy) — the TPU form of
    ``ServerAggregator.test`` (``ml/aggregator/default_aggregator.py``)."""
    base_loss = get_loss_fn(hp.loss)

    def eval_fn(variables: dict, x: jax.Array, y: jax.Array, n_valid: jax.Array):
        n = x.shape[0]
        n_batches = n // batch_size

        def body(carry, i):
            loss_sum, correct, seen = carry
            bx = jax.lax.dynamic_slice_in_dim(x, i * batch_size, batch_size)
            by = jax.lax.dynamic_slice_in_dim(y, i * batch_size, batch_size)
            pos = i * batch_size + jnp.arange(batch_size)
            mask = (pos < n_valid).astype(jnp.float32)
            logits = model.apply(variables, bx, train=False)
            logits = logits.astype(jnp.float32)
            if logits.ndim == by.ndim + 1:
                per = optax.softmax_cross_entropy_with_integer_labels(logits, by)
                pred_ok = (jnp.argmax(logits, -1) == by).astype(jnp.float32)
                if per.ndim == 2:  # sequence task: mean over time
                    per = per.mean(-1)
                    pred_ok = pred_ok.mean(-1)
            else:
                per = optax.sigmoid_binary_cross_entropy(logits, by).mean(-1)
                pred_ok = ((logits > 0) == (by > 0.5)).astype(jnp.float32).mean(-1)
            return (
                loss_sum + jnp.sum(per * mask),
                correct + jnp.sum(pred_ok * mask),
                seen + jnp.sum(mask),
            ), None

        with jax.named_scope("fl.eval"):  # names the ops in a device profile
            (loss_sum, correct, seen), _ = jax.lax.scan(
                body, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), jnp.arange(n_batches)
            )
            seen = jnp.maximum(seen, 1.0)
            return {"test_loss": loss_sum / seen, "test_acc": correct / seen}

    return eval_fn
