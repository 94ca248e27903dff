"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. constructs abstract state via ``jax.eval_shape`` (no allocation),
  3. ``jax.jit(step, in_shardings=..., out_shardings=...)``
     ``.lower(**input_specs(...)).compile()``,
  4. records ``memory_analysis()`` (fits?), ``cost_analysis()``
     (FLOPs/bytes) and the collective-byte census parsed from the
     optimized HLO — the inputs to EXPERIMENTS.md §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_config
from ..models.config import SHAPES
from ..models.model import decode_step, init_model, prefill
from ..train.train_step import TrainConfig, init_train_state, make_train_step
from ..train.optimizer import OptConfig
from .mesh import make_production_mesh
from .sharding import (
    batch_specs_for,
    cache_specs,
    logits_spec,
    param_specs,
)
from .specs import cell_applicable, input_specs

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _bytes_of_shapes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_census(hlo_text: str) -> dict[str, dict[str, float]]:
    """Sum *result* sizes of every collective op in the optimized HLO."""
    census: dict[str, dict[str, float]] = {
        k: {"count": 0, "bytes": 0} for k in _COLLECTIVES
    }
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s*([\w\-]+)\(", line)
        if not m:
            continue
        result_type, opname = m.groups()
        for coll in _COLLECTIVES:
            if opname.startswith(coll):
                census[coll]["count"] += 1
                census[coll]["bytes"] += _bytes_of_shapes(result_type)
                break
    census["total_bytes"] = sum(
        v["bytes"] for k, v in census.items() if isinstance(v, dict)
    )
    return census


def _spec_tree_to_shardings(mesh, tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_lowered(arch: str, shape_name: str, mesh, *, microbatches=None):
    """Construct the jitted step for one cell and lower it (no compile)."""
    from ..models import runtime_flags as _rtf
    from .mesh import dp_size

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return None, why

    # §Perf iteration 5/7: shard-local MoE dispatch (shard_map)
    if cfg.is_moe and shape.global_batch % dp_size(mesh) == 0:
        _rtf.set_moe_groups(dp_size(mesh))
        from .mesh import batch_axes
        _rtf.set_moe_mesh(mesh, batch_axes(mesh))
    else:
        _rtf.set_moe_groups(1)
        _rtf.set_moe_mesh(None)

    specs = input_specs(cfg, shape_name)

    # kv chunking: bound attention working set; bigger chunk for decode.
    kv_chunk = 2048 if shape.seq_len > 8192 else 1024

    if shape.kind == "train":
        if microbatches is not None:
            mb = microbatches
        elif cfg.d_model >= 3584:
            # §Perf: the two big-model train cells (dbrx, zamba2) blow the
            # 16 GiB temp envelope at mb=8 -> halve the live microbatch.
            mb = 16 if shape.global_batch >= 64 else 1
        else:
            mb = 8 if shape.global_batch >= 64 else 1
        tcfg = TrainConfig(
            opt=OptConfig(), microbatches=mb, compress_grads=True,
            kv_chunk=kv_chunk,
        )
        state_tpl = jax.eval_shape(
            lambda: init_train_state(
                init_model(jax.random.key(0), cfg), tcfg
            )
        )
        state_specs = param_specs(mesh, state_tpl)
        batch_specs = batch_specs_for(
            mesh, specs["batch"], batch=shape.global_batch
        )
        step_fn = make_train_step(cfg, tcfg)
        jitted = jax.jit(
            step_fn,
            in_shardings=(
                _spec_tree_to_shardings(mesh, state_specs),
                _spec_tree_to_shardings(mesh, batch_specs),
            ),
            out_shardings=(
                _spec_tree_to_shardings(mesh, state_specs),
                NamedSharding(mesh, P()),
            ),
            donate_argnums=(0,),
        )
        with mesh:
            lowered = jitted.lower(state_tpl, specs["batch"])
        return lowered, ""

    params_tpl = jax.eval_shape(lambda: init_model(jax.random.key(0), cfg))
    # serving replicates weights over "data" (TP only) — see sharding.py —
    # but only when weights/TP fit the HBM budget; dbrx-132b (16.5 GiB/dev
    # TP-only) keeps FSDP sharding + per-layer gathers instead.
    param_bytes = sum(
        leaf.size * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(params_tpl)
    )
    tp = mesh.shape["model"]
    serve_ok = param_bytes / tp < 8 * 2**30
    p_specs = param_specs(mesh, params_tpl,
                          mode="serve" if serve_ok else "train")

    if shape.kind == "prefill":
        batch_specs = batch_specs_for(
            mesh, specs["batch"], batch=shape.global_batch
        )
        cache_tpl = jax.eval_shape(
            lambda: __import__("repro.models.model", fromlist=["init_cache"])
            .init_cache(cfg, batch=shape.global_batch, seq_len=shape.seq_len)
        )
        c_specs = cache_specs(mesh, cache_tpl, cfg, batch=shape.global_batch)
        jitted = jax.jit(
            lambda params, batch: prefill(params, batch, cfg, kv_chunk=kv_chunk),
            in_shardings=(
                _spec_tree_to_shardings(mesh, p_specs),
                _spec_tree_to_shardings(mesh, batch_specs),
            ),
            out_shardings=(
                NamedSharding(mesh, logits_spec(mesh, batch=shape.global_batch)),
                _spec_tree_to_shardings(mesh, c_specs),
            ),
        )
        with mesh:
            lowered = jitted.lower(params_tpl, specs["batch"])
        return lowered, ""

    # decode
    cache_tpl = specs["cache"]
    c_specs = cache_specs(mesh, cache_tpl, cfg, batch=shape.global_batch)
    tok_specs = batch_specs_for(
        mesh, specs["tokens"], batch=shape.global_batch
    )
    jitted = jax.jit(
        lambda params, cache, tokens: decode_step(params, cache, tokens, cfg),
        in_shardings=(
            _spec_tree_to_shardings(mesh, p_specs),
            _spec_tree_to_shardings(mesh, c_specs),
            _spec_tree_to_shardings(mesh, tok_specs),
        ),
        out_shardings=(
            NamedSharding(mesh, logits_spec(mesh, batch=shape.global_batch)),
            _spec_tree_to_shardings(mesh, c_specs),
        ),
        donate_argnums=(1,),
    )
    with mesh:
        lowered = jitted.lower(params_tpl, cache_tpl, specs["tokens"])
    return lowered, ""


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str | None):
    multi = mesh_kind == "multi"
    mesh = make_production_mesh(multi_pod=multi)
    t0 = time.time()
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "mesh_shape": dict(mesh.shape), "status": "ok",
    }
    try:
        lowered, why = build_lowered(arch, shape_name, mesh)
        if lowered is None:
            result["status"] = "skipped"
            result["reason"] = why
            print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: SKIP ({why})")
            return result
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        census = collective_census(hlo)
        result.update(
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            memory=dict(
                argument_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
                output_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
                temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
                generated_code_bytes=int(
                    getattr(mem, "generated_code_size_in_bytes", 0)
                ),
            ),
            flops=float(cost.get("flops", -1.0)),
            transcendentals=float(cost.get("transcendentals", 0.0)),
            bytes_accessed=float(cost.get("bytes accessed", -1.0)),
            collectives=census,
        )
        print(
            f"[dryrun] {arch} x {shape_name} x {mesh_kind}: OK "
            f"compile={t2 - t1:.1f}s flops={result['flops']:.3e} "
            f"bytes={result['bytes_accessed']:.3e} "
            f"coll={census['total_bytes']:.3e}B "
            f"temp={result['memory']['temp_bytes']/2**30:.2f}GiB"
        )
    except Exception as e:  # noqa: BLE001 - report, continue the sweep
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: ERROR {e}")
    finally:
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fn = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_kind}.json")
            with open(fn, "w") as f:
                json.dump(result, f, indent=1, default=str)
    return result


#: placeholder host devices the production meshes are laid out on
HOST_DEVICES = 512


def main():
    # the CPU device count is fixed when the backend starts; importing
    # repro starts none, so setting it here (not at import) is in time
    jax.config.update("jax_num_cpu_devices", HOST_DEVICES)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                results.append(run_cell(arch, shape, mk, args.out))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
