import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, fits, and yield its roofline terms — without TPU hardware.

The two lines above MUST run before any jax import (jax locks device count
at first init): the dry-run sees 512 host devices so `make_production_mesh`
can build the (16,16) single-pod and (2,16,16) multi-pod meshes. Nothing
here allocates real arrays — all inputs/state are ShapeDtypeStructs.

Per cell we record: memory_analysis (fits 16 GB?), cost_analysis (FLOPs /
HBM bytes per device), the collective-byte breakdown parsed from the
compiled HLO, and the derived roofline terms (EXPERIMENTS.md §Dry-run /
§Roofline read these JSONs).

Usage:
  python -m repro.launch.dryrun --arch gemma2_2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, applicable, get_config
from ..configs.base import ModelConfig, ShapeConfig, TrainConfig
from ..configs.registry import ARCH_IDS
from ..models import EPContext, build_model
from ..models.model import default_positions
from ..train import optimizer as opt
from ..train.train_step import TrainState, make_train_step
from . import hlo_analysis as hlo
from .mesh import make_production_mesh, make_test_mesh
from .partitioning import Partitioner, batch_shardings

# dry-run per-arch training overrides: the big MoEs need bf16 moments to fit
TRAIN_OVERRIDES = {
    "arctic_480b": dict(opt_state_dtype="bfloat16"),
    "dbrx_132b": dict(opt_state_dtype="bfloat16"),
}

# §Perf hillclimb variants: named {model:..., train:...} deltas vs baseline
VARIANTS: dict[str, dict] = {
    "a2a_moe": {"model": dict(moe_layout="a2a")},      # HC1: token-routed EP
    "int8_xpod": {"train": dict(grad_compression="int8",
                                opt_state_dtype="float32")},  # HC2: DCN diet
    "remat_none": {"model": dict(remat="none")},       # memory/compute probe
    "remat_dots": {"model": dict(remat="dots")},       # HC2: 2x weight gathers
    # HC1 final: token-routed EP + 4-way microbatching. In the a2a layout
    # microbatching is collectively ~free (weights never move; a2a bytes
    # are token-linear and total-invariant), while token-linear transients
    # shrink 4x — the memory lever the gather layout can't afford.
    "a2a_mb4": {"model": dict(moe_layout="a2a"),
                "train": dict(microbatches=4)},
    "mb2": {"train": dict(microbatches=2)},            # borderline-fit train cells
    "a2a_mb8": {"model": dict(moe_layout="a2a"),
                "train": dict(microbatches=8)},
    "kv_int8": {"model": dict(kv_cache_dtype="int8")},  # decode memory diet
}


# --------------------------------------------------------------------------- inputs


def input_specs(cfg: ModelConfig, shape: ShapeConfig, part: Partitioner) -> dict:
    """ShapeDtypeStruct stand-ins (weak-type-correct, sharded, no alloc)."""
    b, s = shape.global_batch, shape.seq_len
    cdtype = jnp.dtype(cfg.compute_dtype)
    if shape.kind == "train":
        specs = {
            "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
            "targets": jax.ShapeDtypeStruct((b, s), jnp.int32),
        }
    elif shape.kind == "prefill":
        specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    else:  # decode: one token against a seq_len cache
        specs = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    if cfg.encoder_layers > 0:
        # stub modality frontend: precomputed frame embeddings
        enc_s = s if shape.kind != "decode" else min(s, 4096)
        specs["src_embeds"] = jax.ShapeDtypeStruct((b, enc_s, cfg.d_model), cdtype)
    if cfg.rope_mode == "mrope" and shape.kind != "decode":
        # stub vision frontend: 3D (t/h/w) position streams
        specs["positions"] = jax.ShapeDtypeStruct((3, b, s), jnp.int32)
    shardings = batch_shardings(part, specs)
    return {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shardings[k])
        for k, v in specs.items()
    }


def state_abstract(bundle, tcfg: TrainConfig, part: Partitioner):
    """Abstract TrainState with shardings attached."""
    params_abs = bundle.abstract()
    axes = bundle.axes
    params = part.tree_abstract(params_abs, axes)
    sdt = jnp.dtype(tcfg.opt_state_dtype)
    mom = part.tree_abstract(
        jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, sdt), params_abs), axes
    )
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(part.mesh, P()))
    residual = None
    if tcfg.grad_compression != "none":
        residual = part.tree_abstract(
            jax.tree.map(lambda l: jax.ShapeDtypeStruct(l.shape, sdt), params_abs),
            axes,
        )
    return TrainState(
        params=params,
        opt=opt.OptState(step=step, mu=mom, nu=mom, residual=residual),
    )


def cache_abstract(bundle, part: Partitioner, batch: int, capacity: int,
                   cross_len: int = 0):
    cache = jax.eval_shape(lambda: bundle.cache_init(batch, capacity, cross_len))
    axes = bundle.cache_axes(batch, capacity, cross_len)
    return part.tree_abstract(cache, axes)


# --------------------------------------------------------------------------- lowering per kind


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, arch: str,
               scan_layers: bool = True, train_overrides: dict | None = None):
    cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
    part = Partitioner(mesh)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    ep = EPContext(mesh=mesh if cfg.is_moe else None, ep_axis="model",
                   dp_axes=dp_axes)
    bundle = build_model(cfg, ep)
    inputs = input_specs(cfg, shape, part)

    if shape.kind == "train":
        tcfg = TrainConfig(**{**TRAIN_OVERRIDES.get(arch, {}),
                              **(train_overrides or {})})
        if tcfg.grad_compression != "none" and "pod" in mesh.shape:
            # the compressed step is shard_map-manual over 'pod': a dim
            # sharded over BOTH pod (manual) and data (auto) is unsupported,
            # so inputs enter pod-sharded only; the embedding-output
            # constraint re-shards over 'data' inside the auto scope.
            inputs = {
                k: jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=NamedSharding(mesh, P("pod")),
                )
                for k, v in inputs.items()
            }
        grad_shardings = part.tree_shardings(bundle.abstract(), bundle.axes)
        step_fn = make_train_step(bundle, tcfg, mesh=mesh, pod_axis="pod",
                                  grad_shardings=grad_shardings)
        state = state_abstract(bundle, tcfg, part)
        lowered = jax.jit(step_fn, donate_argnums=(0,)).lower(state, inputs)
        tokens = shape.tokens
    elif shape.kind == "prefill":
        params = part.tree_abstract(bundle.abstract(), bundle.axes)
        lowered = jax.jit(bundle.prefill_fn).lower(params, inputs)
        tokens = shape.tokens
    else:  # decode
        params = part.tree_abstract(bundle.abstract(), bundle.axes)
        b = shape.global_batch
        cross_len = min(shape.seq_len, 4096) if cfg.encoder_layers else 0
        cache = cache_abstract(bundle, part, b, shape.seq_len, cross_len)
        if cfg.rope_mode == "mrope":
            pos = jax.ShapeDtypeStruct(
                (3, b, 1), jnp.int32,
                sharding=part.sharding((3, b, 1), (None, "batch", None)),
            )
        else:
            pos = jax.ShapeDtypeStruct(
                (b, 1), jnp.int32,
                sharding=part.sharding((b, 1), ("batch", None)),
            )
        clen = jax.ShapeDtypeStruct((), jnp.int32,
                                    sharding=NamedSharding(mesh, P()))
        lowered = jax.jit(bundle.decode_fn, donate_argnums=(3,)).lower(
            params, inputs["tokens"], pos, cache, clen
        )
        tokens = shape.global_batch  # one new token per sequence
    return lowered, tokens


# --------------------------------------------------------------------------- cell runner


def run_cell(arch: str, shape_name: str, mesh_name: str, out_dir: Path,
             reduced: bool = False, mesh=None, variant: str = "") -> dict:
    cfg = get_config(arch)
    train_overrides = None
    if variant:
        v = VARIANTS[variant]
        cfg = dataclasses.replace(cfg, **v.get("model", {}))
        train_overrides = v.get("train")
    if reduced:
        cfg = cfg.reduce(param_dtype="bfloat16", compute_dtype="bfloat16")
    shape = SHAPES[shape_name]
    if reduced:
        shape = dataclasses.replace(
            shape, seq_len=min(shape.seq_len, 256),
            global_batch=max(mesh.shape.get("pod", 1) * mesh.shape.get("data", 1) * 2, 8)
            if mesh else 8,
        )
    ok, reason = applicable(cfg, shape)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "status": "skip", "reason": reason,
        "variant": variant,
    }
    if not ok:
        _write(out_dir, result)
        return result

    if mesh is None:
        mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    try:
        # (1) the deployed artifact: scan-over-layers + remat. This is what
        # memory_analysis must be read from (the real activation schedule).
        with jax.set_mesh(mesh):
            lowered, tokens = lower_cell(cfg, shape, mesh, arch,
                                         train_overrides=train_overrides)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            coll_scanned = hlo.collective_bytes(compiled.as_text())
            cost_scanned = compiled.cost_analysis()
        t_main = time.time() - t0

        # (2) XLA's cost_analysis counts a while-loop (scan) body ONCE, so
        # FLOPs/bytes/collective counts from (1) undercount by ~group_count.
        # Fix: compile depth-1 and depth-2 UNROLLED probes and extrapolate —
        # cost(G) = cost(d1) + (G-1) * (cost(d2) - cost(d1)) — exact for
        # homogeneous scan groups (which scan already requires).
        def probe(depth: int):
            pcfg = dataclasses.replace(
                cfg,
                num_layers=len(cfg.block_pattern) * depth + len(cfg.tail_pattern),
                encoder_layers=depth if cfg.encoder_layers else 0,
            )
            with jax.set_mesh(mesh):
                low, _ = lower_cell(pcfg, shape, mesh, arch, scan_layers=False,
                                    train_overrides=train_overrides)
                comp = low.compile()
                return comp.cost_analysis(), hlo.collective_bytes(comp.as_text())

        g = cfg.group_count
        if cfg.encoder_layers:
            assert cfg.encoder_layers == g, "probe scaling needs equal depths"
        cost1, coll1 = probe(1)
        cost2, coll2 = probe(2)

        def extrap(key, c1, c2):
            a, b = float(c1.get(key, 0.0)), float(c2.get(key, 0.0))
            return a + (g - 1) * max(b - a, 0.0)

        flops = extrap("flops", cost1, cost2)
        hbm_bytes = extrap("bytes accessed", cost1, cost2)
        coll = {
            k: int(coll1[k] + (g - 1) * max(coll2[k] - coll1[k], 0))
            for k in coll1
        }
        total, active = cfg.param_count()
        roof = hlo.Roofline(
            flops=flops,
            hbm_bytes=hbm_bytes,
            coll_bytes=float(coll["total"]),
            model_flops=hlo.model_flops_for(shape.kind, total, active, tokens),
            chips=chips,
        )
        roof_d = roof.to_dict()
        roof_d["t_collective_bf16eq_s"] = coll["total_bf16eq"] / hlo.ICI_BW
        result.update(
            status="ok",
            seconds_compile=round(t_main, 1),
            seconds_probes=round(time.time() - t0 - t_main, 1),
            memory=hlo.summarize_memory(mem),
            collectives=coll,
            collectives_scanned_raw={k: int(v) for k, v in coll_scanned.items()},
            cost_scanned_raw={
                "flops": float(cost_scanned.get("flops", 0.0)),
                "bytes_accessed": float(cost_scanned.get("bytes accessed", 0.0)),
            },
            roofline=roof_d,
            params_total=total,
            params_active=active,
            tokens=tokens,
        )
    except Exception as e:  # record the failure — dry-run bugs are OUR bugs
        result.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    _write(out_dir, result)
    return result


def _write(out_dir: Path, result: dict) -> None:
    out_dir.mkdir(parents=True, exist_ok=True)
    name = f"{result['arch']}__{result['shape']}__{result['mesh']}.json"
    if result.get("variant"):
        name = name.replace(".json", f"__{result['variant']}.json")
    (out_dir / name).write_text(json.dumps(result, indent=1))


# --------------------------------------------------------------------------- CLI


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, choices=ARCH_IDS)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both", "test"])
    ap.add_argument("--all", action="store_true", help="all archs x shapes")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced configs on a small test mesh (CI)")
    ap.add_argument("--variant", default="", choices=[""] + list(VARIANTS),
                    help="§Perf hillclimb config delta")
    args = ap.parse_args()

    out = Path(args.out)
    archs = ARCH_IDS if (args.all or args.arch is None) else (args.arch,)
    shapes = list(SHAPES) if (args.all or args.shape is None) else (args.shape,)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = 0
    for mesh_name in meshes:
        test_mesh = None
        if mesh_name == "test":
            test_mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
        for arch in archs:
            for shape_name in shapes:
                r = run_cell(arch, shape_name, mesh_name, out,
                             reduced=args.reduced, mesh=test_mesh,
                             variant=args.variant)
                line = (f"[dryrun] {arch:22s} {shape_name:12s} {mesh_name:6s} "
                        f"{args.variant or '-':8s} {r['status']}")
                if r["status"] == "ok":
                    roof = r["roofline"]
                    line += (
                        f" bottleneck={roof['bottleneck']:10s}"
                        f" t={max(roof['t_compute_s'], roof['t_memory_s'], roof['t_collective_s'])*1e3:9.2f}ms"
                        f" peak/dev={r['memory']['peak_estimate_bytes']/2**30:7.2f}GiB"
                        f" compile={r['seconds_compile']:.0f}s"
                    )
                elif r["status"] == "error":
                    failures += 1
                    line += f" {r['error'][:120]}"
                else:
                    line += f" ({r['reason'][:80]})"
                print(line, flush=True)
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
