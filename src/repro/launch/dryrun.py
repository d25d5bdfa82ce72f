import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count at first init.
#   This is set ONLY here (never in conftest/pyproject) so tests and benches
#   see the real single CPU device.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape x mesh) cell:
  jit(step).lower(**input_specs).compile()
on the single-pod (16,16) and multi-pod (2,16,16) production meshes, printing
``memory_analysis()`` (fits-in-HBM proof) and ``cost_analysis()`` (FLOPs /
bytes for §Roofline), plus collective bytes parsed from the post-SPMD HLO.

Results are appended as JSON under reports/dryrun/ — benchmarks/roofline.py
derives the three roofline terms from them.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --arch all --shape all [--multi-pod]
  python -m repro.launch.dryrun ... --quantize serve   (W8A8 Tensorizer path)
"""

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, shape_by_name, SHAPES
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models import steps as ST

REPORT_DIR = Path(__file__).resolve().parents[3] / "reports" / "dryrun"

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of an HLO shape string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in the (per-device) HLO.

    CPU-backend caveat (measured, §Perf cell A): XLA's float-normalization
    pass promotes bf16 collectives to f32 on CPU ("..._promoted" reduction
    computations with a convert fused in front). On the TPU *target* those
    collectives run at bf16, so promoted ops are counted at half — the true
    wire payload of the lowered program on v5e.
    """
    out = {k: 0 for k in _COLLECTIVES}
    count = {k: 0 for k in _COLLECTIVES}
    promoted_bytes = 0
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+([a-z\-]+)", line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        base = op.replace("-start", "")
        if base in _COLLECTIVES and not op.endswith("-done"):
            b = _shape_bytes(shape_str)
            if "_promoted" in line:          # CPU bf16->f32 promotion artifact
                promoted_bytes += b // 2
                b //= 2
            out[base] += b
            count[base] += 1
    return {"bytes": out, "counts": count, "total_bytes": sum(out.values()),
            "cpu_promotion_discount_bytes": promoted_bytes}


def build_cell(cfg, shape):
    """Returns (step_fn, example_args_sds, donate) for a cell."""
    params = ST.param_sds(cfg)
    if shape.kind == "train":
        opt = ST.opt_sds(cfg, params)
        batch = ST.batch_specs(cfg, shape)
        step = jax.ShapeDtypeStruct((), jnp.int32)
        fn = ST.make_train_step(cfg)
        return fn, (params, opt, batch, step), (0, 1)
    if shape.kind == "prefill":
        batch = ST.batch_specs(cfg, shape)
        return ST.make_prefill_step(cfg), (params, batch), ()
    # decode
    cache = ST.cache_specs(cfg, shape)
    batch = ST.batch_specs(cfg, shape)
    return ST.make_decode_step(cfg), (params, cache, batch), (1,)


def reduced_depths(cfg):
    """(cfg_hi, cfg_lo, units_hi, units_lo, units_full) for the exact-cost
    extrapolation: cost(full) = cost(lo) + (U_full - U_lo) * marginal, with
    marginal = (cost(hi) - cost(lo)) / (U_hi - U_lo) from UNROLLED compiles.
    Family-aware so every depth unit is a true repeated block."""
    if cfg.family == "encdec":
        # enc and dec layer counts move together (both 12 in the config)
        hi = cfg.replace(n_layers=3, n_enc_layers=3, scan_unroll=True)
        lo = cfg.replace(n_layers=2, n_enc_layers=2, scan_unroll=True)
        return hi, lo, 3, 2, cfg.n_layers
    if cfg.family == "hybrid":
        tail = cfg.n_layers % cfg.attn_every
        g_full = cfg.n_layers // cfg.attn_every
        hi = cfg.replace(n_layers=2 * cfg.attn_every + tail, scan_unroll=True)
        lo = cfg.replace(n_layers=1 * cfg.attn_every + tail, scan_unroll=True)
        return hi, lo, 2, 1, g_full          # units = shared-block groups
    if cfg.family == "ssm":
        hi = cfg.replace(n_layers=4, scan_unroll=True)   # 2 pairs
        lo = cfg.replace(n_layers=2, scan_unroll=True)   # 1 pair
        return hi, lo, 2, 1, cfg.n_layers // 2           # units = pairs
    hi = cfg.replace(n_layers=3, scan_unroll=True)
    lo = cfg.replace(n_layers=2, scan_unroll=True)
    return hi, lo, 3, 2, cfg.n_layers


def _compile_once(cfg, shape, donate_ok=True):
    fn, args, donate = build_cell(cfg, shape)
    lowered = jax.jit(fn, donate_argnums=donate if donate_ok else ()).lower(*args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    return compiled, cost, collective_bytes(hlo), len(hlo)


def run_cell(arch: str, shape_name: str, multi_pod: bool, quantize: str = "off",
             overrides: dict | None = None, tag: str = "") -> dict:
    cfg = get_config(arch)
    if quantize != "off":
        cfg = cfg.replace(quantize=quantize)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = shape_by_name(shape_name)
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "kind": shape.kind,
        "quantize": quantize, "tag": tag, "status": "skipped",
    }
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        rec["reason"] = "full-attention arch: long_500k requires sub-quadratic decode (DESIGN.md)"
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with shd.use_mesh(mesh):
        # ---- pass 1: full depth, scan mode — the memory / sharding proof ----
        compiled, cost_scan, coll_scan, hlo_bytes = _compile_once(cfg, shape)
        t_full = time.time() - t0
        try:
            mem = compiled.memory_analysis()
            mem_d = {
                "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_size_in_bytes": getattr(mem, "output_size_in_bytes", None),
                "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
                "generated_code_size_in_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not implement it
            mem_d = {"error": str(e)}

        # ---- passes 2+3: reduced depth, UNROLLED — exact per-layer costs ----
        t1 = time.time()
        cfg_hi, cfg_lo, u_hi, u_lo, u_full = reduced_depths(cfg)
        _, cost_hi, coll_hi, _ = _compile_once(cfg_hi, shape, donate_ok=False)
        _, cost_lo, coll_lo, _ = _compile_once(cfg_lo, shape, donate_ok=False)
        t_cost = time.time() - t1

        def extrap(hi: float, lo: float) -> float:
            marginal = (hi - lo) / (u_hi - u_lo)
            return lo + (u_full - u_lo) * marginal

        flops = extrap(cost_hi.get("flops", 0.0), cost_lo.get("flops", 0.0))
        bytes_acc = extrap(cost_hi.get("bytes accessed", 0.0),
                           cost_lo.get("bytes accessed", 0.0))
        coll_total = extrap(coll_hi["total_bytes"], coll_lo["total_bytes"])
        coll_by_op = {
            k: extrap(coll_hi["bytes"][k], coll_lo["bytes"][k]) for k in coll_hi["bytes"]
        }

        rec.update(
            status="ok",
            n_devices=int(mesh.devices.size),
            compile_full_s=round(t_full, 2),
            compile_cost_s=round(t_cost, 2),
            flops=flops,
            bytes_accessed=bytes_acc,
            collective_bytes=coll_total,
            collective_bytes_by_op=coll_by_op,
            collective_counts_hi=coll_hi["counts"],
            flops_scan_mode_raw=cost_scan.get("flops"),
            collectives_scan_mode_raw=coll_scan,
            extrapolation={"units_full": u_full, "units_hi": u_hi, "units_lo": u_lo,
                           "flops_hi": cost_hi.get("flops"), "flops_lo": cost_lo.get("flops")},
            memory=mem_d,
            model_params=cfg.param_count(),
            model_params_active=cfg.active_param_count(),
            tokens=shape.global_batch * (1 if shape.kind == "decode" else shape.seq_len),
            hlo_bytes=hlo_bytes,
        )
    return rec


def save(rec: dict) -> Path:
    REPORT_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"_{rec['tag']}" if rec.get("tag") else ""
    q = f"_q{rec['quantize']}" if rec.get("quantize", "off") != "off" else ""
    p = REPORT_DIR / f"{rec['arch']}_{rec['shape']}_{rec['mesh']}{q}{tag}.json"
    p.write_text(json.dumps(rec, indent=1))
    return p


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quantize", default="off")
    ap.add_argument("--tag", default="")
    ap.add_argument("--override", default="", help="k=v,k=v config overrides")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch.replace("-", "_").replace(".", "_")]
    shapes = [s.name for s in SHAPES] if args.shape == "all" else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    overrides = {}
    for kv in filter(None, args.override.split(",")):
        k, v = kv.split("=")
        overrides[k] = {"true": True, "false": False}.get(v.lower(), v)
        if isinstance(overrides[k], str):
            for caster in (int, float):
                try:
                    overrides[k] = caster(v)
                    break
                except ValueError:
                    pass

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    rec = run_cell(arch, shape, mp, args.quantize, overrides, args.tag)
                    p = save(rec)
                    if rec["status"] == "ok":
                        print(f"[dryrun] OK   {label}: compile={rec['compile_full_s']}+{rec['compile_cost_s']}s "
                              f"flops={rec['flops']:.3e} coll={rec['collective_bytes']:.3e}B -> {p.name}",
                              flush=True)
                    else:
                        print(f"[dryrun] SKIP {label}: {rec.get('reason','')}", flush=True)
                except Exception as e:
                    failures += 1
                    print(f"[dryrun] FAIL {label}: {type(e).__name__}: {e}", flush=True)
                    traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
