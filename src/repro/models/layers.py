"""Shared layers + the ParamSpec machinery.

Params are plain pytrees (nested dicts of jnp arrays). Every leaf is
declared by a :class:`ParamSpec` carrying its **logical axes** — the names
`launch.partitioning` later maps onto mesh axes. This keeps model code free
of any sharding syntax while making every array's distribution explicit and
auditable (the MaxText/flax "logical axis rules" pattern, without a
framework dependency).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import AxisType, get_abstract_mesh

# logical axis vocabulary (see launch/partitioning.py for the mesh rules)
LAYERS, EMBED, MLP, VOCAB = "layers", "embed", "mlp", "vocab"
QHEADS, KVHEADS, HEADDIM = "q_heads", "kv_heads", "head"
EXPERTS, LRU, SSM_INNER, SSM_STATE, SSM_HEADS = (
    "experts", "lru", "ssm_inner", "ssm_state", "ssm_heads",
)
EXPERTS_DP = "experts_dp"  # a2a MoE layout: expert dim sharded over 'data'.


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    init: str = "normal"     # normal | zeros | ones | embed | small
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def stack_specs(specs: Any, n: int) -> Any:
    """Prepend a scanned 'layers' axis to every spec in a tree."""
    return jax.tree.map(
        lambda s: ParamSpec((n, *s.shape), (LAYERS, *s.axes), s.init, s.scale),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def init_params(specs: Any, key: jax.Array, dtype: jnp.dtype) -> Any:
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )
    keys = jax.random.split(key, len(leaves))

    def one(spec: ParamSpec, k: jax.Array) -> jax.Array:
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, dtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, dtype)
        fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
        if spec.init == "embed":
            std = 0.02  # GPT-style: keeps tied-head logits near-uniform at init
        elif spec.init == "small":
            std = 0.02
        else:
            std = spec.scale / np.sqrt(max(fan_in, 1))
        return (jax.random.normal(k, spec.shape, jnp.float32) * std).astype(dtype)

    return jax.tree.unflatten(treedef, [one(s, k) for s, k in zip(leaves, keys)])


def param_axes(specs: Any) -> Any:
    return jax.tree.map(
        lambda s: s.axes, specs, is_leaf=lambda x: isinstance(x, ParamSpec)
    )


def abstract_params(specs: Any, dtype: jnp.dtype) -> Any:
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# --------------------------------------------------------------------------- activation constraints

BATCH_AXES = ("pod", "data")
MODEL_AXIS = "model"


def constrain(x: jax.Array, names: tuple) -> jax.Array:
    """`with_sharding_constraint` that no-ops without a mesh context.

    ``names`` entries: None, a mesh-axis name, or a tuple of axis names;
    axes absent from the ambient mesh are dropped. GSPMD's unconstrained
    propagation can pick pathological layouts (e.g. replicating the batch
    dim and all-reducing full activations — observed on the 512-device
    dry-run before these pins existed); block-boundary constraints make the
    Megatron-style layout (batch over ('pod','data'), d_model replicated,
    heads/ffn over 'model') explicit.
    """
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.shape:
        return x
    # inside a partial-manual shard_map (e.g. the int8 cross-pod step is
    # manual over 'pod'), Manual axes must not appear in constraints
    types = dict(zip(mesh.axis_names, getattr(mesh, "axis_types", ())))
    manual = AxisType.Manual

    def usable(a: str) -> bool:
        return a in mesh.shape and types.get(a) != manual

    parts = []
    for n in names:
        if n is None:
            parts.append(None)
        elif isinstance(n, tuple):
            axes = tuple(a for a in n if usable(a))
            parts.append(axes if axes else None)
        else:
            parts.append(n if usable(n) else None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*parts)
    )


def constrain_bsd(x: jax.Array) -> jax.Array:
    """(B, S, D) residual-stream layout: batch over ('pod','data') and —
    when the sequence divides the model axis — seq over 'model'
    (Megatron-style *sequence parallelism*). SP is what bounds activation
    residency under scan-over-layers: the per-group saved carry shrinks by
    the model-axis size (granite train_4k: 30 GiB -> <2 GiB per device),
    at the cost of an all-gather/reduce-scatter pair per block that GSPMD
    inserts at the layout switch. Decode (S=1) and CPU tests fall back to
    batch-only sharding automatically.
    """
    mesh = get_abstract_mesh()
    seq_axis = None
    if mesh is not None and "model" in mesh.shape:
        types = dict(zip(mesh.axis_names, getattr(mesh, "axis_types", ())))
        if (x.shape[1] > 1 and x.shape[1] % mesh.shape["model"] == 0
                and types.get("model") != AxisType.Manual):
            seq_axis = MODEL_AXIS
    return constrain(x, (BATCH_AXES, seq_axis, None))


def constrain_bshd(x: jax.Array) -> jax.Array:
    """(B, S, H, Dh) attention layout: batch + heads sharded."""
    return constrain(x, (BATCH_AXES, None, MODEL_AXIS, None))


def gather_sp(x: jax.Array) -> jax.Array:
    """Leave SP layout: gather the seq dim to full (batch-only sharding).

    Placed explicitly on the *bf16 norm output* feeding each mixer/FFN:
    without the pin, XLA parks the SP->full resharding all-gather on the
    first f32 op inside the consumer (norm internals, rope), moving 2x the
    wire bytes (measured on arctic train_4k; EXPERIMENTS.md §Perf HC1-i2).
    """
    return constrain(x, (BATCH_AXES, None, None))


# --------------------------------------------------------------------------- norms


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * scale).astype(dt) * (1.0 + gamma.astype(dt))


def rms_norm_spec(dim: int, axis_name: str = EMBED) -> ParamSpec:
    # gamma is stored as an offset from 1 (gemma convention) so zeros-init
    return ParamSpec((dim,), (axis_name,), init="zeros")


def qk_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMS norm over the head dim (qwen3's qk_norm)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * scale).astype(dt) * (1.0 + gamma.astype(dt))


# --------------------------------------------------------------------------- softcap


def softcap(x: jax.Array, cap: float) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap <= 0:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# --------------------------------------------------------------------------- RoPE


def rope_frequencies(head_dim: int, theta: float) -> np.ndarray:
    half = head_dim // 2
    return 1.0 / theta ** (np.arange(0, half, dtype=np.float32) / half)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    *,
    theta: float = 10_000.0,
    mode: str = "full",
    sections: tuple[int, ...] = (),
) -> jax.Array:
    """Rotary embedding, three variants.

    x: (B, S, H, D). positions: (B, S) int — or (3, B, S) for mode='mrope'
    (temporal/height/width position streams, Qwen2-VL).

    full: rotate all D dims. half: rotate only the first D/2 dims (ChatGLM's
    2D/partial RoPE — the rest carries un-rotated content). mrope: the D/2
    frequency slots are split into `sections` groups, each driven by its own
    position stream.
    """
    b, s, h, d = x.shape
    if mode == "half":
        rot, keep = jnp.split(x, 2, axis=-1)
        return jnp.concatenate(
            [apply_rope(rot, positions, theta=theta, mode="full"), keep], axis=-1
        )
    half = d // 2
    freqs = jnp.asarray(rope_frequencies(d, theta))  # (half,)
    if mode == "mrope":
        assert positions.ndim == 3 and sum(sections) == half, (
            positions.shape, sections, half)
        parts = []
        start = 0
        for sec, pos in zip(sections, positions):
            ang = pos[..., None].astype(jnp.float32) * freqs[start : start + sec]
            parts.append(ang)
            start += sec
        angles = jnp.concatenate(parts, axis=-1)  # (B, S, half)
    else:
        angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- MLP


def mlp_specs(d_model: int, d_ff: int, act: str) -> dict[str, ParamSpec]:
    specs = {
        "w_up": ParamSpec((d_model, d_ff), (EMBED, MLP)),
        "w_down": ParamSpec((d_ff, d_model), (MLP, EMBED)),
    }
    if act in ("swiglu", "geglu"):
        specs["w_gate"] = ParamSpec((d_model, d_ff), (EMBED, MLP))
    return specs


def mlp_apply(params: dict, x: jax.Array, act: str) -> jax.Array:
    up = x @ params["w_up"]
    if act == "swiglu":
        up = jax.nn.silu(x @ params["w_gate"]) * up
    elif act == "geglu":
        up = jax.nn.gelu(x @ params["w_gate"], approximate=True) * up
    elif act == "gelu":
        up = jax.nn.gelu(up, approximate=True)
    else:
        raise ValueError(act)
    return up @ params["w_down"]


# --------------------------------------------------------------------------- embedding


def embed_specs(vocab: int, d_model: int, tie: bool) -> dict[str, ParamSpec]:
    specs = {"table": ParamSpec((vocab, d_model), (VOCAB, EMBED), init="embed")}
    if not tie:
        specs["head"] = ParamSpec((d_model, vocab), (EMBED, VOCAB))
    return specs


def embed_lookup(params: dict, tokens: jax.Array, d_model: int) -> jax.Array:
    x = params["table"][tokens]
    # gemma-style sqrt(d) scaling keeps tied-embedding logits sane
    return x * jnp.asarray(np.sqrt(d_model), x.dtype)


def embed_logits(params: dict, x: jax.Array) -> jax.Array:
    if "head" in params:
        return x @ params["head"]
    return x @ params["table"].T
