"""Mixture-of-Experts with expert-parallel dispatch.

Routing is GShard/Switch-style top-k with capacity + drop: positions within
an expert come from a one-hot cumsum over the (token, slot) stream, tokens
past `capacity` are dropped (their gate mass simply doesn't contribute —
the residual stream carries them). Dispatch/combine are scatter/gather, not
the O(T·E·C) dispatch-einsum, so memory stays ~2× activations.

Two execution paths with identical math:
  * local  — whole expert set on this shard (CPU tests / no mesh);
  * EP     — `jax.shard_map` over the model axis: tokens are replicated
    across it (they're the attention output), each shard computes its
    E/ep_size experts, and a psum over the model axis sums the per-shard
    partial outputs. No all-to-all is needed in this formulation; the psum
    is the only collective, which is what the dry-run HLO shows.

Aux losses (load-balance + router-z) are computed from the full router
distribution (identical on every EP shard) and psum-averaged over the data
axes only.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from .layers import EMBED, EXPERTS, EXPERTS_DP, MLP, ParamSpec, mlp_apply, mlp_specs


@dataclasses.dataclass(frozen=True)
class EPContext:
    """How the MoE layer should parallelize. None mesh => local path."""

    mesh: Optional[jax.sharding.Mesh] = None
    ep_axis: str = "model"
    dp_axes: tuple[str, ...] = ("data",)


def moe_specs(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    if cfg.moe_layout == "a2a":
        # experts over 'data' (dp-EP), per-expert F over 'model' (TP): weights
        # never move — tokens do, via all-to-all (see moe_apply_a2a)
        ax_up = (EXPERTS_DP, EMBED, MLP)
        ax_down = (EXPERTS_DP, MLP, EMBED)
    else:
        ax_up = (EXPERTS, EMBED, MLP)
        ax_down = (EXPERTS, MLP, EMBED)
    specs: dict = {
        "router": ParamSpec((d, e), (EMBED, None), init="small"),
        "w_gate": ParamSpec((e, d, f), ax_up),
        "w_up": ParamSpec((e, d, f), ax_up),
        "w_down": ParamSpec((e, f, d), ax_down),
    }
    if cfg.moe_dense_residual:
        specs["dense"] = mlp_specs(d, cfg.moe_dense_d_ff or cfg.d_ff, cfg.act)
    return specs


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    return max(
        int(np.ceil(cfg.capacity_factor * cfg.top_k * tokens / cfg.num_experts)), 1
    )


def _route_and_compute(
    x2d: jax.Array,            # (T, D) this shard's tokens
    params: dict,
    cfg: ModelConfig,
    e_start: jax.Array,        # first global expert id on this shard
    e_local: int,              # experts on this shard
    capacity: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (y2d partial output, lb_loss, z_loss). fp32 router."""
    t, d = x2d.shape
    k, e = cfg.top_k, cfg.num_experts
    logits = (x2d.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, k)           # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )

    flat_ids = expert_ids.reshape(-1)                          # (T*k,) token-major
    onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)      # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1    # rank within expert
    keep = pos < capacity
    local_sel = (flat_ids >= e_start) & (flat_ids < e_start + e_local) & keep

    dest = (flat_ids - e_start) * capacity + pos               # (T*k,)
    dest = jnp.where(local_sel, dest, e_local * capacity)      # OOB => dropped
    x_rep = jnp.repeat(x2d, k, axis=0)                         # matches flat_ids order
    buf = jnp.zeros((e_local * capacity, d), x2d.dtype)
    buf = buf.at[dest].add(
        x_rep * local_sel[:, None].astype(x2d.dtype), mode="drop"
    )
    h = buf.reshape(e_local, capacity, d)

    up = jnp.einsum("ecd,edf->ecf", h, params["w_up"])
    if cfg.act in ("swiglu", "geglu"):
        gate = jnp.einsum("ecd,edf->ecf", h, params["w_gate"])
        act = jax.nn.silu(gate) if cfg.act == "swiglu" else jax.nn.gelu(
            gate, approximate=True
        )
        up = act * up
    else:
        up = jax.nn.gelu(up, approximate=True)
    y = jnp.einsum("ecf,efd->ecd", up, params["w_down"])

    y_flat = y.reshape(e_local * capacity, d)
    contrib = jnp.take(y_flat, jnp.minimum(dest, e_local * capacity - 1), axis=0)
    weight = (gate_vals.reshape(-1) * local_sel).astype(x2d.dtype)
    y2d = (contrib * weight[:, None]).reshape(t, k, d).sum(axis=1)

    # Switch load-balance: E * sum_e f_e * p_e over the *global* expert set
    frac = onehot.astype(jnp.float32).mean(axis=0) * k         # assignment fraction
    mean_p = probs.mean(axis=0)
    lb = e * jnp.sum(frac / k * mean_p)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return y2d, lb, z


def _a2a_wire(x: jax.Array, axis_name: str) -> jax.Array:
    """tiled all-to-all whose wire dtype is pinned to bf16 in BOTH the
    forward and the transpose (a2a is its own transpose here). Without the
    pin, XLA runs the exchange at whatever precision the fused neighborhood
    uses — measured f32 on arctic (2x DCN bytes for zero benefit)."""

    dtype = x.dtype  # closed over: custom_vjp residuals must be jax types

    @jax.custom_vjp
    def go(x):
        return jax.lax.all_to_all(
            x.astype(jnp.bfloat16), axis_name, split_axis=0, concat_axis=0,
            tiled=True,
        ).astype(dtype)

    def fwd(x):
        return go(x), None

    def bwd(_, g):
        gg = jax.lax.all_to_all(
            g.astype(jnp.bfloat16), axis_name, split_axis=0, concat_axis=0,
            tiled=True,
        )
        return (gg.astype(dtype),)

    go.defvjp(fwd, bwd)
    return go(x)


def moe_apply_a2a(
    params: dict,
    x: jax.Array,              # (B, S, D)
    cfg: ModelConfig,
    ep: EPContext,
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """a2a expert parallelism (§Perf HC1): experts sharded over 'data' on
    the expert dim, per-expert FFN width over 'model'. Weights never move;
    *tokens* are routed to their experts' owners with one all-to-all and
    routed back with another. vs the gather layout this removes (i) the
    3x-per-layer FSDP weight all-gathers and (ii) the expert-gradient
    all-reduce entirely (experts are owned, not replicated — their grads
    arrive through the a2a transpose). Measured on arctic-480b train_4k:
    see EXPERIMENTS.md §Perf.
    """
    mesh = ep.mesh
    b, s, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    manual = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    n_data = mesh.shape.get("data", 1)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_size = int(np.prod([mesh.shape[a] for a in batch_axes])) if batch_axes else 1
    e_local = e // n_data
    f_local = cfg.d_ff // mesh.shape.get("model", 1)
    P = jax.sharding.PartitionSpec
    cap = _capacity((b // dp_size) * s, cfg)

    def local_fn(x_loc, router, wg, wu, wd):
        bl, sl, _ = x_loc.shape
        t = bl * sl
        x2d = x_loc.reshape(t, d).astype(jnp.dtype(cfg.compute_dtype))
        logits = x2d.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

        flat_ids = expert_ids.reshape(-1)
        onehot = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        keep = pos < cap
        dest = jnp.where(keep, flat_ids * cap + pos, e * cap)
        x_rep = jnp.repeat(x2d, k, axis=0)
        send = jnp.zeros((e * cap, d), x2d.dtype).at[dest].add(
            x_rep * keep[:, None].astype(x2d.dtype), mode="drop"
        ).reshape(e, cap, d)

        recv = _a2a_wire(send, "data") if n_data > 1 else send
        # recv[i*e_local + le] = sender i's capacity slots for my expert le
        h = recv.reshape(n_data, e_local, cap, d).transpose(1, 0, 2, 3) \
            .reshape(e_local, n_data * cap, d)

        up = jnp.einsum("ecd,edf->ecf", h, wu)
        if cfg.act in ("swiglu", "geglu"):
            g = jnp.einsum("ecd,edf->ecf", h, wg)
            act = jax.nn.silu(g) if cfg.act == "swiglu" else jax.nn.gelu(
                g, approximate=True)
            up = act * up
        else:
            up = jax.nn.gelu(up, approximate=True)
        y = jnp.einsum("ecf,efd->ecd", up, wd)      # partial over 'model'
        y = y.astype(x2d.dtype)                     # bf16 on the wire

        back = y.reshape(e_local, n_data, cap, d).transpose(1, 0, 2, 3) \
            .reshape(e, cap, d)
        if n_data > 1:
            back = _a2a_wire(back, "data")
        y_flat = back.reshape(e * cap, d)
        contrib = jnp.take(y_flat, jnp.minimum(dest, e * cap - 1), axis=0)
        w = (gate_vals.reshape(-1) * keep).astype(x2d.dtype)
        y2d = (contrib * w[:, None]).reshape(t, k, d).sum(axis=1)
        if "model" in mesh.shape:
            y2d = jax.lax.psum(y2d, "model")        # sum the F-partials

        frac = onehot.astype(jnp.float32).mean(axis=0) * k
        lb = e * jnp.sum(frac / k * probs.mean(axis=0))
        z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        if batch_axes:
            lb = jax.lax.psum(lb, batch_axes) / dp_size
            z = jax.lax.psum(z, batch_axes) / dp_size
        return y2d.reshape(bl, sl, d), lb, z

    wspec_up = P("data" if "data" in mesh.shape else None, None,
                 "model" if "model" in mesh.shape else None)
    wspec_down = P("data" if "data" in mesh.shape else None,
                   "model" if "model" in mesh.shape else None, None)
    y, lb, z = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(batch_axes if batch_axes else None, None, None),
            P(None, None),
            wspec_up, wspec_up, wspec_down,
        ),
        out_specs=(P(batch_axes if batch_axes else None, None, None), P(), P()),
        axis_names=set(manual),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return y, {"lb": lb, "z": z}


def moe_apply(
    params: dict,
    x: jax.Array,              # (B, S, D)
    cfg: ModelConfig,
    ep: EPContext = EPContext(),
) -> tuple[jax.Array, dict[str, jax.Array]]:
    """Returns (output, {'lb': load-balance loss, 'z': router z loss})."""
    b, s, d = x.shape

    if (
        cfg.moe_layout == "a2a"
        and ep.mesh is not None
        and cfg.num_experts % max(ep.mesh.shape.get("data", 1), 1) == 0
        and cfg.d_ff % max(ep.mesh.shape.get("model", 1), 1) == 0
    ):
        y, aux = moe_apply_a2a(params, x, cfg, ep)
        if cfg.moe_dense_residual and "dense" in params:
            y = y + mlp_apply(params["dense"], x, cfg.act)
        return y, aux

    if ep.mesh is None or ep.ep_axis not in ep.mesh.shape:
        x2d = x.reshape(b * s, d)
        cap = _capacity(b * s, cfg)
        y2d, lb, z = _route_and_compute(
            x2d, params, cfg, jnp.int32(0), cfg.num_experts, cap
        )
        y = y2d.reshape(b, s, d)
    else:
        mesh = ep.mesh
        ep_size = mesh.shape[ep.ep_axis]
        assert cfg.num_experts % ep_size == 0, (cfg.num_experts, ep_size)
        e_local = cfg.num_experts // ep_size
        dp = tuple(a for a in ep.dp_axes if a in mesh.shape)
        dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1
        assert b % dp_size == 0, (b, dp_size)
        cap = _capacity((b // dp_size) * s, cfg)
        P = jax.sharding.PartitionSpec

        expert_p = {
            k2: P(ep.ep_axis, *([None] * (v.ndim - 1)))
            for k2, v in params.items()
            if k2 in ("w_gate", "w_up", "w_down")
        }

        def local_fn(x_loc, router, wg, wu, wd):
            bl, sl, _ = x_loc.shape
            eid = jax.lax.axis_index(ep.ep_axis) * e_local
            y2d, lb, z = _route_and_compute(
                x_loc.reshape(bl * sl, d),
                {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd},
                cfg, eid, e_local, cap,
            )
            y_loc = jax.lax.psum(y2d.reshape(bl, sl, d), ep.ep_axis)
            denom = dp_size
            if dp:
                lb = jax.lax.psum(lb, dp) / denom
                z = jax.lax.psum(z, dp) / denom
            return y_loc, lb, z

        y, lb, z = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(
                P(dp if dp else None, None, None),
                P(None, None),
                expert_p["w_gate"],
                expert_p["w_up"],
                expert_p["w_down"],
            ),
            out_specs=(P(dp if dp else None, None, None), P(), P()),
            check_vma=False,
        )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])

    if cfg.moe_dense_residual and "dense" in params:
        y = y + mlp_apply(params["dense"], x, cfg.act)
    return y, {"lb": lb, "z": z}
