"""Train-step factories for the transformer pool and the paper's RNNs.

Both return pure jit/pjit-compatible functions over an explicit TrainState
pytree.  The update pipeline is:

    grads -> [optional ternary compression + DP all-reduce via shard_map]
          -> clip -> AdamW/SGD -> master-weight clip to [-alpha, alpha]

The final clip is the paper's algorithm (keeps Bernoulli probabilities in
[0,1]); it is applied only to quantizable 'W*' leaves.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import bnlstm as BL
from repro.core.qlinear import clip_tree
from repro.models import transformer as T
from repro.train import compress as C
from repro.train.optimizer import OptConfig, OptState, opt_init, opt_update

Array = jax.Array


class TrainState(NamedTuple):
    params: Any
    opt: OptState
    rng: Array
    # RNN-only: batch-norm running statistics (None for transformers)
    bn_state: Any = None
    # gradient-compression error-feedback residual (None = compression off)
    residual: Any = None


def train_state_init(params: Any, opt_cfg: OptConfig, rng: Array,
                     bn_state: Any = None, compress: bool = False) -> TrainState:
    return TrainState(
        params=params,
        opt=opt_init(params, opt_cfg),
        rng=rng,
        bn_state=bn_state,
        residual=jax.tree.map(jnp.zeros_like, params) if compress else None,
    )


# ---------------------------------------------------------------------------
# compressed-DP key hygiene (shared by both families)
# ---------------------------------------------------------------------------


def _dp_step_keys(rng: Array, data_axes) -> tuple[Array, Array]:
    """Split one per-step key into (model key, compression key) inside a
    shard_map region.

    Two distinct hazards, two distinct fixes: (1) the model key (dropout /
    stochastic quantization inside loss_fn) must be a DIFFERENT key from the
    one driving `compress_tree`'s stochastic ternarization, or the two
    random processes are correlated; (2) the rng arrives REPLICATED (in_spec
    P()), so without decorrelation every data replica draws identical
    compression randomness — correlated quantization noise that the
    cross-replica mean cannot average away, defeating the error-feedback
    variance reduction.  Folding each data axis' `axis_index` into the
    compression key gives every replica an independent stream while the
    model key stays replicated (matching the unsharded path's semantics of
    one global-batch dropout draw per step)."""
    k_model, k_comp = jax.random.split(rng)
    for ax in data_axes:
        k_comp = jax.random.fold_in(k_comp, jax.lax.axis_index(ax))
    return k_model, k_comp


# ---------------------------------------------------------------------------
# transformer pool
# ---------------------------------------------------------------------------


def make_train_step(cfg, opt_cfg: OptConfig,
                    mesh=None, compress_grads: bool = False) -> Callable:
    """Returns step(state, batch) -> (state, metrics).

    With `compress_grads` and a mesh, per-replica gradients are ternarized
    (error feedback) inside shard_map before the data-parallel mean — the
    all-reduce then moves 2-bit codes instead of fp32 (DESIGN.md §4).
    """

    def loss_fn(params, batch, rng):
        return T.lm_loss(params, batch, cfg, training=True, rng=rng)

    def apply_updates(state: TrainState, grads, metrics):
        params, opt, m2 = opt_update(grads, state.opt, state.params, opt_cfg)
        params = clip_tree(params, cfg.quant)
        metrics.update(m2)
        return params, opt, metrics

    if not (compress_grads and mesh is not None):
        def step(state: TrainState, batch) -> tuple[TrainState, dict]:
            rng, sub = jax.random.split(state.rng)
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch, sub)
            metrics = {"loss": loss, **aux}
            params, opt, metrics = apply_updates(state, grads, metrics)
            return state._replace(params=params, opt=opt, rng=rng), metrics

        return step

    # Compressed-DP variant: local grads inside shard_map over the data axes.
    # This path is pure data parallelism (each replica holds full params and
    # ternarizes its local gradient before the cross-replica mean); combine
    # with TP by nesting meshes at the launcher level.
    from jax import shard_map
    from repro.runtime import use_mesh

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = P(data_axes if len(data_axes) > 1 else data_axes[0])
    rep = P()

    def local_grads(params, batch, rng, residual):
        k_model, k_comp = _dp_step_keys(rng, data_axes)
        # inside shard_map the mesh axes are Manual: the model's internal
        # with_sharding_constraint calls must become no-ops
        with use_mesh(None):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, k_model)
        grads, new_res = C.compress_tree(grads, k_comp, residual)
        mean = lambda t: jax.tree.map(
            lambda x: jax.lax.pmean(x, data_axes), t)
        # the residual is pmean'd too: per-replica randomness makes the raw
        # residuals genuinely diverge, and the carried TrainState.residual is
        # replicated (out_spec P()).  The mean residual preserves the exact
        # aggregate conservation law — mean(emitted) + mean(new_res) ==
        # mean(grads) + old_res — so no signal is lost across steps.
        return mean(grads), mean(new_res), mean(loss), mean(aux["nll"])

    def step(state: TrainState, batch) -> tuple[TrainState, dict]:
        rng, sub = jax.random.split(state.rng)
        batch_specs = jax.tree.map(
            lambda x: P(bspec[0], *([None] * (x.ndim - 1))), batch)
        fn = shard_map(
            local_grads, mesh=mesh,
            in_specs=(rep, batch_specs, rep, rep),
            out_specs=(rep, rep, rep, rep),
            check_vma=False)
        grads, new_res, loss, nll = fn(state.params, batch, sub, state.residual)
        metrics = {"loss": loss, "nll": nll}
        params, opt, metrics = apply_updates(state, grads, metrics)
        return state._replace(params=params, opt=opt, rng=rng,
                              residual=new_res), metrics

    return step


# ---------------------------------------------------------------------------
# the paper's BN-LSTM / BN-GRU
# ---------------------------------------------------------------------------


def make_rnn_train_step(cfg: BL.RNNConfig, opt_cfg: OptConfig,
                        mesh=None, compress_grads: bool = False) -> Callable:
    """step(state, batch, lr_scale=1.0) -> (state, metrics) for the faithful
    reproduction.  Threads BN running statistics through the state (paper
    Eq. 3).  `lr_scale` (traced scalar) is the plateau-schedule hook: the
    launcher feeds `PlateauLR.update(val_bpc)` through it without retracing.

    With `compress_grads` and a mesh, the same ternary-compressed
    data-parallel pipeline as the transformer pool runs on the paper's own
    model: per-replica gradients are ternarized (error feedback) inside
    shard_map before the cross-replica mean.  BN batch statistics are then
    per-replica (local-batch BN) with the running stats pmean'd — the
    standard sync-free recurrent-BN compromise; the uncompressed path keeps
    exact global-batch statistics."""

    def loss_fn(params, bn_state, tokens, targets, rng):
        loss, new_bn = BL.lm_loss({"params": params, "state": bn_state},
                                  tokens, targets, cfg, training=True, rng=rng)
        return loss, new_bn

    def apply_updates(state: TrainState, grads, lr_scale):
        params, opt, m2 = opt_update(grads, state.opt, state.params, opt_cfg,
                                     lr_scale)
        return BL.clip_masters(params, cfg), opt, m2

    if not (compress_grads and mesh is not None):
        def step(state: TrainState, batch,
                 lr_scale: Array | float = 1.0) -> tuple[TrainState, dict]:
            rng, sub = jax.random.split(state.rng)
            (loss, new_bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, state.bn_state,
                batch["tokens"], batch["targets"], sub)
            metrics = {"loss": loss, "bpc": loss / jnp.log(2.0)}
            params, opt, m2 = apply_updates(state, grads, lr_scale)
            metrics.update(m2)
            return state._replace(params=params, opt=opt, rng=rng,
                                  bn_state=new_bn), metrics

        return step

    from jax import shard_map
    from repro.runtime import use_mesh

    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    bspec = P(data_axes if len(data_axes) > 1 else data_axes[0])
    rep = P()

    def local_grads(params, bn_state, tokens, targets, rng, residual):
        k_model, k_comp = _dp_step_keys(rng, data_axes)
        with use_mesh(None):
            (loss, new_bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, bn_state, tokens, targets, k_model)
        grads, new_res = C.compress_tree(grads, k_comp, residual)
        mean = lambda t: jax.tree.map(
            lambda x: jax.lax.pmean(x, data_axes), t)
        return mean(grads), mean(new_res), mean(loss), mean(new_bn)

    def step(state: TrainState, batch,
             lr_scale: Array | float = 1.0) -> tuple[TrainState, dict]:
        rng, sub = jax.random.split(state.rng)
        tspec = P(bspec[0], None)
        fn = shard_map(
            local_grads, mesh=mesh,
            in_specs=(rep, rep, tspec, tspec, rep, rep),
            out_specs=(rep, rep, rep, rep),
            check_vma=False)
        grads, new_res, loss, new_bn = fn(
            state.params, state.bn_state, batch["tokens"], batch["targets"],
            sub, state.residual)
        metrics = {"loss": loss, "bpc": loss / jnp.log(2.0)}
        params, opt, m2 = apply_updates(state, grads, lr_scale)
        metrics.update(m2)
        return state._replace(params=params, opt=opt, rng=rng,
                              bn_state=new_bn, residual=new_res), metrics

    return step


def make_rnn_eval(cfg: BL.RNNConfig) -> Callable:
    def evaluate(state: TrainState, batch) -> dict:
        loss, _ = BL.lm_loss({"params": state.params, "state": state.bn_state},
                             batch["tokens"], batch["targets"], cfg,
                             training=False)
        return {"loss": loss, "bpc": loss / jnp.log(2.0)}

    return evaluate
