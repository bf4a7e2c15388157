"""train_step: loss -> grads -> (optionally compressed) reduction -> AdamW.

Microbatch gradient accumulation is a `lax.scan` over batch slices with an
fp32 gradient accumulator (k× smaller activation peak at the cost of one
extra gradient-sized buffer). The compressed variant wraps the whole step
in ``jax.shard_map(axis_names={'pod'})``: *within* a pod everything stays
GSPMD-auto (ICI-fast reductions), while the **cross-pod gradient mean is an
explicit int8 all-gather over the DCN** with error-feedback residuals —
4× fewer wire bytes on the slowest fabric tier. This is the
distributed-optimization half of the paper's economics: like the swarm, it
attacks the bytes crossing the expensive pipe.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import TrainConfig
from ..models.model import ModelBundle
from . import optimizer as opt

Params = Any


class TrainState(NamedTuple):
    params: Params
    opt: opt.OptState

    @property
    def step(self) -> jax.Array:
        return self.opt.step


def init_train_state(bundle: ModelBundle, tcfg: TrainConfig,
                     key: jax.Array) -> TrainState:
    params = bundle.init(key)
    return TrainState(params=params, opt=opt.adamw_init(params, tcfg))


def _grads_and_metrics(bundle: ModelBundle, tcfg: TrainConfig,
                       params: Params, batch: dict):
    """Plain or accumulated gradient computation (fp32 accumulator)."""
    k = tcfg.microbatches
    if k <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            bundle.loss_fn, has_aux=True
        )(params, batch)
        return grads, metrics

    def slice_mb(x, i):
        # all batch-dict arrays are batch-leading (tokens/targets/src_embeds)
        mb = x.shape[0] // k
        return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)

    def body(carry, i):
        acc, _ = carry
        mb_batch = {kk: slice_mb(v, i) for kk, v in batch.items()}
        (loss, metrics), g = jax.value_and_grad(
            bundle.loss_fn, has_aux=True
        )(params, mb_batch)
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
        return (acc, metrics), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    dummy_metrics = jax.eval_shape(
        lambda p, b: bundle.loss_fn(p, b)[1], params,
        {kk: slice_mb(v, 0) for kk, v in batch.items()},
    )
    dummy_metrics = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), dummy_metrics)
    (acc, metrics), _ = jax.lax.scan(
        body, (zeros, dummy_metrics), jnp.arange(k)
    )
    grads = jax.tree.map(lambda g: (g / k), acc)
    return grads, metrics


def make_train_step(
    bundle: ModelBundle,
    tcfg: TrainConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    pod_axis: Optional[str] = None,
    grad_shardings=None,
):
    """Returns jit-able ``train_step(state, batch) -> (state, metrics)``.

    ``grad_shardings`` (a NamedSharding tree matching params): constrains
    gradients to the parameters' FSDP layout right at the jax.grad output,
    which lets XLA emit **reduce-scatter** for the data-axis gradient
    reduction instead of all-reduce + slice (§Perf HC2-i3 — without the
    pin, every measured HLO had reduce-scatter=0 and paid ~2x wire bytes
    on its largest collective).

    If ``tcfg.grad_compression == "int8"`` and the mesh has ``pod_axis``,
    the cross-pod mean runs in int8 (see module docstring); otherwise the
    reduction is whatever GSPMD emits (fp32/bf16 all-reduce).
    """
    compress = (
        tcfg.grad_compression == "int8"
        and mesh is not None
        and pod_axis is not None
        and pod_axis in mesh.shape
        and mesh.shape[pod_axis] > 1
    )

    def plain_step(state: TrainState, batch: dict):
        grads, metrics = _grads_and_metrics(bundle, tcfg, state.params, batch)
        if grad_shardings is not None:
            grads = jax.tree.map(
                jax.lax.with_sharding_constraint, grads, grad_shardings
            )
        params, ostate, ometrics = opt.adamw_update(
            grads, state.opt, state.params, tcfg
        )
        return TrainState(params, ostate), {**metrics, **ometrics}

    if not compress:
        return plain_step

    npods = mesh.shape[pod_axis]
    P = jax.sharding.PartitionSpec

    def pod_local_step(state: TrainState, batch: dict):
        # grads here are the *pod-local* mean (loss averaged over the pod's
        # batch slice; GSPMD reduces over the in-pod data axis only, since
        # 'pod' is a manual axis in this scope).
        grads, metrics = _grads_and_metrics(bundle, tcfg, state.params, batch)
        q, scales, new_resid = opt.quantize_grads_with_feedback(
            grads, state.opt.residual
        )

        def xpod_mean(qt, st):
            qg = jax.lax.all_gather(qt, pod_axis)          # int8 on the DCN
            sg = jax.lax.all_gather(st, pod_axis)          # (P,) fp32 scales
            return jnp.einsum(
                "p...,p->...", qg.astype(jnp.float32), sg
            ) / npods

        mean_grads = jax.tree.map(xpod_mean, q, scales)
        ostate = state.opt._replace(residual=new_resid)
        params, ostate, ometrics = opt.adamw_update(
            mean_grads, ostate, state.params, tcfg
        )
        metrics = {
            k: jax.lax.pmean(v, pod_axis) for k, v in {**metrics, **ometrics}.items()
        }
        return TrainState(params, ostate), metrics

    def compressed_step(state: TrainState, batch: dict):
        batch_specs = {k: P(pod_axis) for k in batch}       # batch split by pod
        return jax.shard_map(
            pod_local_step,
            mesh=mesh,
            in_specs=(P(), batch_specs),                    # params/opt replicated across pods
            out_specs=(P(), P()),
            axis_names={pod_axis},                          # manual over pod, auto elsewhere
            check_vma=False,
        )(state, batch)

    return compressed_step


def make_eval_step(bundle: ModelBundle):
    def eval_step(params: Params, batch: dict):
        _, metrics = bundle.loss_fn(params, batch)
        return metrics

    return eval_step
