import os

if __name__ == "__main__":
    # CLI mode only: must precede any jax import (same contract as
    # launch/dryrun.py).  Guarded so ``import benchmarks.roofline``
    # (run.py's --roofline annotation path) stays side-effect free.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=512 "
        + os.environ.get("XLA_FLAGS", "")
    )

"""Roofline analysis from compiled dry-run artifacts.

Methodology (EXPERIMENTS.md §Roofline):
  * ``compiled.cost_analysis()`` is PER-DEVICE after SPMD partitioning
    (calibrated: a 4-way sharded matmul reports 1/4 of the global
    flops), and XLA counts every while-loop body ONCE — scan trip
    counts are NOT multiplied in.
  * We therefore lower each cell twice as an UNROLLED PROBE with
    n_layers = 1 and = 2 (all structural scans unrolled via
    ``runtime_flags``), take the marginal per-layer cost, and
    extrapolate:  total(L) = fixed + L * per_layer; the microbatch
    scan multiplies the fwd/bwd part analogously.
  * Collective bytes come from the same probes' optimized HLO
    (result-shape census over all-gather/all-reduce/reduce-scatter/
    all-to-all/collective-permute), extrapolated the same way.

Terms (v5e constants: 197 TF/s bf16, 819 GB/s HBM, 50 GB/s/link ICI):
  compute    = flops_per_device / 197e12        [s]
  memory     = hbm_bytes_per_device / 819e9     [s]
  collective = coll_bytes_per_device / 50e9     [s]

Usage:
  python -m benchmarks.roofline --arch qwen3_0_6b --shape train_4k
  python -m benchmarks.roofline --all --out experiments/roofline
"""
import argparse
import dataclasses
import json

import jax

#: published per-chip peaks, keyed by ``jax.Device.device_kind``.  A
#: device whose kind is missing here is an error, never a default.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 16 GB of HBM at 819 GB/s).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}

PEAK_FLOPS = DEVICE_PEAKS["TPU v5 lite"]["bf16_flops"]
HBM_BW = DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_s"]
ICI_BW = 50e9
CHIPS = 256

#: the host's measured STREAM-triad bandwidth, GB/s (once per process).
#: It is the CPU's roof, reported as ``cpu_stream_gbs`` — never as a
#: device peak.
_CPU_STREAM_GBS: list = []

#: the achieved-fraction column that goes with each roof's name
FRACTION_KEY = {"peak_gbs": "roofline_frac",
                "cpu_stream_gbs": "cpu_stream_frac"}


def measure_stream_gbs(n: int = 1 << 24, reps: int = 3) -> float:
    """Measured STREAM-triad bandwidth of the host, GB/s.

    ``a = b + s * c`` over f64 vectors sized well past LLC: 3 streams
    of 8 bytes per element per iteration.  Best of ``reps`` — the roof
    is the *capability*, not the average.
    """
    import time as _time

    import numpy as np

    b = np.random.default_rng(0).random(n)
    c = np.random.default_rng(1).random(n)
    best = float("inf")
    for _ in range(reps):
        t0 = _time.perf_counter()
        a = b + 2.5 * c
        dt = _time.perf_counter() - t0
        best = min(best, dt)
    del a
    return 3 * 8 * n / best / 1e9


def bandwidth_roof(device=None) -> tuple:
    """``(name, GB/s)`` of ``device``'s bandwidth roof.

    An accelerator's roof is its published HBM peak from
    :data:`DEVICE_PEAKS` (``"peak_gbs"``; an unknown ``device_kind``
    raises).  The CPU's is the host's measured STREAM triad
    (``"cpu_stream_gbs"``).
    """
    if device is None:
        device = jax.devices()[0]
    if device.platform == "cpu":
        if not _CPU_STREAM_GBS:
            _CPU_STREAM_GBS.append(measure_stream_gbs())
        return "cpu_stream_gbs", _CPU_STREAM_GBS[0]
    try:
        peaks = DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device.device_kind!r} "
            f"({device.platform}); add it to DEVICE_PEAKS with its source"
        ) from None
    return "peak_gbs", peaks["hbm_bytes_s"] / 1e9


def annotate_roofline(rows, device=None) -> int:
    """Add achieved-vs-roof columns to kernel rows in place.

    Every row dict carrying a ``bandwidth_gbs`` value gains the roof
    under its name (``peak_gbs`` on an accelerator, ``cpu_stream_gbs``
    on the CPU) and the achieved fraction (``roofline_frac`` /
    ``cpu_stream_frac``).  Returns how many rows were annotated.
    """
    name, roof = bandwidth_roof(device)
    frac_key = FRACTION_KEY[name]
    annotated = 0
    for r in rows:
        if "bandwidth_gbs" not in r:
            continue
        r[name] = round(roof, 2)
        r[frac_key] = round(float(r["bandwidth_gbs"]) / roof, 4)
        annotated += 1
    return annotated


def probe_cell(arch: str, shape_name: str, *, mesh_kind: str = "single"):
    """Lower unrolled depth-2/3 probes; extrapolate to the full stack.

    L=1 probes were observed to trigger pathological partitioning
    choices (non-monotone costs), so marginals come from L=2 -> 3.
    Heterogeneous stacks (hybrid/vlm/gemma local:global) get a third
    probe isolating the auxiliary block's cost.
    """
    from repro.configs import get_config
    from repro.models import runtime_flags
    from repro.models.config import SHAPES
    from repro.launch.dryrun import build_lowered, collective_census
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    KEYS = ("flops", "bytes", "coll")

    def lower_probe(**overrides):
        small = dataclasses.replace(cfg, **overrides)
        import repro.configs as configs_mod
        orig = configs_mod.get_config
        configs_mod.get_config = (
            lambda name: small if name == arch else orig(name)
        )
        import repro.launch.dryrun as dr
        orig_dr = dr.get_config
        dr.get_config = configs_mod.get_config
        runtime_flags.set_unroll(True)
        try:
            lowered, why = build_lowered(arch, shape_name, mesh,
                                         microbatches=1)
        finally:
            runtime_flags.set_unroll(1)
            configs_mod.get_config = orig
            dr.get_config = orig_dr
        if lowered is None:
            return None, why
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        census = collective_census(compiled.as_text())
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(census["total_bytes"]),
        }, ""

    ok, why = __import__(
        "repro.launch.specs", fromlist=["cell_applicable"]
    ).cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}

    L = cfg.n_layers
    fam = cfg.family

    if fam == "hybrid" and cfg.hybrid_attn_every:
        # pA/pB: pure-mamba stacks isolate the mamba marginal; pC adds
        # exactly one shared-attention invocation.
        pA, w = lower_probe(n_layers=2, hybrid_attn_every=0, family="ssm")
        pB, _ = lower_probe(n_layers=3, hybrid_attn_every=0, family="ssm")
        pC, _ = lower_probe(n_layers=2, hybrid_attn_every=2)
        m = {k: pB[k] - pA[k] for k in KEYS}
        fixed = {k: pA[k] - 2 * m[k] for k in KEYS}
        a = {k: max(pC[k] - pA[k], 0.0) for k in KEYS}
        n_attn = L // cfg.hybrid_attn_every
        total = {k: max(fixed[k] + L * m[k] + n_attn * a[k], 0.0)
                 for k in KEYS}
        per_layer = m
    elif fam == "vlm" and cfg.cross_attn_every:
        pA, w = lower_probe(n_layers=2, cross_attn_every=0, family="dense")
        pB, _ = lower_probe(n_layers=3, cross_attn_every=0, family="dense")
        pC, _ = lower_probe(n_layers=2, cross_attn_every=2)
        m = {k: pB[k] - pA[k] for k in KEYS}
        fixed = {k: pA[k] - 2 * m[k] for k in KEYS}
        a = {k: max(pC[k] - pA[k], 0.0) for k in KEYS}
        n_cross = L // cfg.cross_attn_every
        total = {k: max(fixed[k] + L * m[k] + n_cross * a[k], 0.0)
                 for k in KEYS}
        per_layer = m
    elif cfg.local_global_every:
        # all-global probes give g and fixed; mixed probe gives local m.
        pG2, w = lower_probe(n_layers=2, local_global_every=0,
                             sliding_window=0)
        pG3, _ = lower_probe(n_layers=3, local_global_every=0,
                             sliding_window=0)
        pM3, _ = lower_probe(n_layers=3, local_global_every=3)  # 2 loc + 1 glob
        g = {k: pG3[k] - pG2[k] for k in KEYS}
        fixed = {k: pG2[k] - 2 * g[k] for k in KEYS}
        m = {k: (pM3[k] - fixed[k] - g[k]) / 2 for k in KEYS}
        n_glob = L // cfg.local_global_every
        total = {k: max(fixed[k] + n_glob * g[k] + (L - n_glob) * m[k], 0.0)
                 for k in KEYS}
        per_layer = m
    else:
        p2, w = lower_probe(n_layers=2, **(
            {"n_enc_layers": 2} if cfg.n_enc_layers else {}))
        p3, _ = lower_probe(n_layers=3, **(
            {"n_enc_layers": 3} if cfg.n_enc_layers else {}))
        per_layer = {k: p3[k] - p2[k] for k in KEYS}
        fixed = {k: p2[k] - 2 * per_layer[k] for k in KEYS}
        total = {k: max(fixed[k] + L * per_layer[k], 0.0) for k in KEYS}

    t_compute = total["flops"] / PEAK_FLOPS
    t_memory = total["bytes"] / HBM_BW
    t_coll = total["coll"] / ICI_BW
    dominant = max(
        ("compute", t_compute), ("memory", t_memory), ("collective", t_coll),
        key=lambda kv: kv[1],
    )[0]

    # MODEL_FLOPS: 6 N D for training, 2 N D for inference (per device)
    n_active = cfg.n_active_params
    tokens = shape.global_batch * (shape.seq_len if shape.kind == "train" else
                                   (shape.seq_len if shape.kind == "prefill"
                                    else 1))
    mult = 6 if shape.kind == "train" else 2
    model_flops_global = mult * n_active * tokens
    model_flops = model_flops_global / CHIPS
    useful = model_flops / total["flops"] if total["flops"] else 0.0

    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "status": "ok",
        "per_device": total,
        "per_layer": per_layer,
        "fixed": fixed,
        "terms_s": {
            "compute": t_compute, "memory": t_memory, "collective": t_coll,
        },
        "dominant": dominant,
        "model_flops_per_device": model_flops,
        "useful_fraction": useful,
        # MFU bound: the model-flop utilization this cell achieves if the
        # step runs exactly at the max of the three roofline terms —
        # the score §Perf drives up.
        "mfu_bound": (
            (model_flops / PEAK_FLOPS) / max(t_compute, t_memory, t_coll)
            if max(t_compute, t_memory, t_coll) > 0 else 0.0
        ),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/roofline")
    args = ap.parse_args()

    from repro.configs import ARCHS
    from repro.models.config import SHAPES

    archs = ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]

    os.makedirs(args.out, exist_ok=True)
    for arch in archs:
        for shape in shapes:
            try:
                r = probe_cell(arch, shape)
            except Exception as e:  # noqa: BLE001
                r = {"arch": arch, "shape": shape, "status": "error",
                     "error": f"{type(e).__name__}: {e}"}
            fn = os.path.join(args.out, f"{arch}__{shape}.json")
            with open(fn, "w") as f:
                json.dump(r, f, indent=1, default=str)
            if r["status"] == "ok":
                t = r["terms_s"]
                print(f"[roofline] {arch} x {shape}: "
                      f"compute={t['compute']:.2e}s memory={t['memory']:.2e}s "
                      f"coll={t['collective']:.2e}s -> {r['dominant']} "
                      f"useful={r['useful_fraction']:.2f}")
            else:
                print(f"[roofline] {arch} x {shape}: {r['status']} "
                      f"{r.get('reason', r.get('error', ''))}")


if __name__ == "__main__":
    main()
