import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any jax import (jax locks the device
count at first init).  For every cell we:

  1. build ShapeDtypeStruct stand-ins (weak-type correct, sharded, no
     allocation) for params / optimizer state / batch / cache,
  2. ``jax.jit(step).lower(...)`` -> ``.compile()`` under the production
     mesh -- sharding mismatches, unsupported collectives and
     compile-time OOMs all surface here,
  3. record cost_analysis / memory_analysis / per-collective bytes into
     experiments/dryrun/*.json (consumed by benchmarks/roofline.py).

Usage:
  python -m repro.launch.dryrun --arch granite-20b --shape train_4k
  python -m repro.launch.dryrun --arch granite-20b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import sys
import time

import jax

from repro.configs import ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import input_specs
from repro.models.config import LM_SHAPES
from repro.roofline.hlo import collective_bytes_from_hlo

OUT_DIR = os.environ.get("REPRO_DRYRUN_DIR",
                         os.path.join(os.path.dirname(__file__),
                                      "../../../experiments/dryrun"))


def _cost_dict(compiled) -> dict:
    """Normalize ``compiled.cost_analysis()`` across jax versions: 0.4.x
    returns a list with one dict per computation, newer jax a dict."""
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return cost


def cell_skip_reason(cfg, shape):
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return ("full softmax attention is O(S) memory per decoded token at "
                "S=524288; skipped per assignment rules (DESIGN.md §5)")
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool, save: bool = True):
    cfg = get_config(arch)
    shape = next(s for s in LM_SHAPES if s.name == shape_name)
    skip = cell_skip_reason(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    result = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
              "kind": shape.kind}
    if skip:
        result["status"] = "skipped"
        result["reason"] = skip
        _save(result, arch, shape_name, mesh_name, save)
        print(f"[dryrun] {cfg.name} x {shape.name} x {mesh_name}: SKIP ({skip})")
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        cell = input_specs(cfg, shape, mesh)
        if cell.kind == "train":
            args = (cell.params, cell.opt, cell.batch)
            jitted = jax.jit(cell.fn, donate_argnums=(0, 1))
        elif cell.kind == "prefill":
            args = (cell.params, cell.batch)
            jitted = jax.jit(cell.fn)
        else:
            args = (cell.params, cell.cache, cell.batch)
            jitted = jax.jit(cell.fn, donate_argnums=(1,))
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        # Gradient accumulation runs under a lax.scan whose body
        # cost_analysis counts ONCE (one microbatch).  For roofline
        # numbers comparable across accum settings, additionally lower an
        # accum_steps=1 variant and take FLOPs / bytes / wire bytes from
        # it; the memory-fit proof stays with the accumulated compile.
        cost_compiled = compiled
        if cell.kind == "train" and cfg.train_accum > 1:
            import dataclasses as _dc
            cfg1 = _dc.replace(cfg, train_accum=1, loss_chunk=None)
            cell1 = input_specs(cfg1, shape, mesh)
            cost_compiled = jax.jit(
                cell1.fn, donate_argnums=(0, 1)).lower(
                cell1.params, cell1.opt, cell1.batch).compile()
            result["accum_steps"] = cfg.train_accum

    cost = _cost_dict(cost_compiled)
    result["status"] = "ok"
    result["lower_s"] = round(t_lower, 2)
    result["compile_s"] = round(t_compile, 2)
    result["flops"] = float(cost.get("flops", 0.0))
    result["bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
    try:
        mem = compiled.memory_analysis()
        result["memory"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}
    except Exception as e:   # CPU backend may not implement it
        result["memory"] = {"error": str(e)[:200]}
    try:
        hlo = cost_compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    result["collectives"] = collective_bytes_from_hlo(hlo)
    _save(result, arch, shape_name, mesh_name, save)
    print(f"[dryrun] {cfg.name} x {shape.name} x {mesh_name}: OK "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
          f"GFLOP {result['flops']/1e9:.1f}, "
          f"coll GB {result['collectives']['total_bytes']/1e9:.3f})")
    return result


def _save(result, arch, shape_name, mesh_name, save):
    if not save:
        return
    os.makedirs(OUT_DIR, exist_ok=True)
    fn = os.path.join(OUT_DIR, f"{arch}__{shape_name}__{mesh_name}.json")
    with open(fn, "w") as fh:
        json.dump(result, fh, indent=1)


def run_calibration(arch: str, shape_name: str, save: bool = True):
    """Lower two small UNROLLED variants (1 and 2 pattern-periods, full
    attention, single-chunk MoE) to measure exact per-period HLO costs.

    cost_analysis counts a lax.scan (while loop) body ONCE regardless of
    trip count, so the full-model numbers undercount the layer stack; the
    difference B - A of the unrolled variants is the exact per-period cost
    (compute, bytes, wire bytes), which benchmarks/roofline.py uses to
    extrapolate: total = full + (n_periods - 1) * per_period.
    """
    import dataclasses
    cfg = get_config(arch)
    shape = next(s for s in LM_SHAPES if s.name == shape_name)
    if cell_skip_reason(cfg, shape):
        return None
    k = len(cfg.pattern)
    mesh = make_production_mesh(multi_pod=False)
    out = {"arch": cfg.name, "shape": shape.name, "variants": {}}
    for label, layers in (("A", k), ("B", 2 * k)):
        # MoE keeps its production chunk size: moe_ffn unrolls the chunk
        # loop in Python under cfg.unroll so every chunk is counted
        # (inflating the chunk would make dispatch cost O(S^2) -- wrong).
        ccfg = dataclasses.replace(
            cfg, n_layers=layers, unroll=True, attn_impl="full",
            train_accum=1, loss_chunk=None)
        t0 = time.time()
        with jax.set_mesh(mesh):
            cell = input_specs(ccfg, shape, mesh)
            if cell.kind == "train":
                args = (cell.params, cell.opt, cell.batch)
            elif cell.kind == "prefill":
                args = (cell.params, cell.batch)
            else:
                args = (cell.params, cell.cache, cell.batch)
            lowered = jax.jit(cell.fn).lower(*args)
            compiled = lowered.compile()
        cost = _cost_dict(compiled)
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        out["variants"][label] = {
            "layers": layers,
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collectives": collective_bytes_from_hlo(hlo),
            "compile_s": round(time.time() - t0, 2),
        }
        print(f"[calib] {arch} x {shape_name} {label}({layers}L): "
              f"GFLOP {out['variants'][label]['flops']/1e9:.2f}")
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        with open(os.path.join(
                OUT_DIR, f"{arch}__{shape_name}__calib.json"), "w") as fh:
            json.dump(out, fh, indent=1)
    return out


def run_paper_cell(algo: str = "d3ca", multi_pod: bool = False,
                   save: bool = True, block_n: int = 40960,
                   block_m: int = 5120, inner_steps: int = None,
                   local_backend: str = "ref"):
    """Dry-run the paper's own doubly distributed workload (hinge SVM) at
    production mesh scale: one (block_n x block_m) block per chip, i.e.
    the paper's weak-scaling cell (40k x 5k) per device.

    The step builders come from the unified solver registry
    (``get_solver(algo).make_step``), so the dry-run lowers exactly the
    shard_map step the ``Solver`` API runs, under either local backend.

    The inner solver is a sequential lax.scan whose body cost_analysis
    counts once; we therefore also lower 1-step and 2-step variants and
    record the per-inner-step delta so the roofline can extrapolate
    total = full + (steps - 1) * (B - A), exactly like the layer-scan
    calibration for the LM archs.
    """
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import (D3CAConfig, RADiSAConfig, get_loss, get_solver)
    import jax.numpy as jnp

    mesh = make_production_mesh(multi_pod=multi_pod)
    daxes = ("pod", "data") if multi_pod else ("data",)
    Pn = 1
    for a in daxes:
        Pn *= mesh.shape[a]
    Qn = mesh.shape["model"]
    n, m = Pn * block_n, Qn * block_m
    mesh_name = "2x16x16" if multi_pod else "16x16"
    inner = inner_steps or block_n     # one local epoch, as the paper

    def sds(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    loss = get_loss("hinge")
    make_step = get_solver(algo).make_step
    x = sds((n, m), P(daxes, "model"))
    y, maskv = sds((n,), P(daxes)), sds((n,), P(daxes))
    key0 = jax.random.PRNGKey(0)
    t_arg = np.int32(1)

    def lower_one(steps):
        if algo == "d3ca":
            step = make_step(
                loss, mesh, D3CAConfig(lam=1e-2, local_steps=steps),
                n=n, n_p=block_n, data_axis=daxes,
                local_backend=local_backend)
            args = (t_arg, key0, x, y, maskv, sds((n,), P(daxes)),
                    sds((m,), P("model")))
        else:
            step = make_step(
                loss, mesh, RADiSAConfig(lam=1e-3, L=steps),
                n=n, n_p=block_n, m_q=block_m, data_axis=daxes,
                local_backend=local_backend)
            args = (t_arg, key0, x, y, maskv, sds((m,), P("model")))
        t0 = time.time()
        lowered = step.lower(*args)
        compiled = lowered.compile()
        cost = _cost_dict(compiled)
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
        out = {
            "steps": int(steps),
            "compile_s": round(time.time() - t0, 2),
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            "collectives": collective_bytes_from_hlo(hlo),
        }
        try:
            mem = compiled.memory_analysis()
            out["memory"] = {
                k: int(getattr(mem, k))
                for k in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes")
                if hasattr(mem, k)}
        except Exception as e:
            out["memory"] = {"error": str(e)[:200]}
        return out

    result = {"arch": f"paper-svm-{algo}", "shape": f"{block_n}x{block_m}",
              "mesh": mesh_name, "kind": "paper", "status": "ok",
              "P": Pn, "Q": Qn, "inner_steps": inner,
              "local_backend": local_backend,
              "full": lower_one(inner),
              "calib_A": lower_one(1), "calib_B": lower_one(2)}
    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        suffix = "" if local_backend == "ref" else f"__{local_backend}"
        fn = os.path.join(
            OUT_DIR, f"paper_svm_{algo}__{mesh_name}{suffix}.json")
        with open(fn, "w") as fh:
            json.dump(result, fh, indent=1)
    f = result["full"]
    print(f"[dryrun] paper-svm-{algo} x {mesh_name}: OK "
          f"(GFLOP {f['flops']/1e9:.2f}, "
          f"coll GB {f['collectives']['total_bytes']/1e9:.3f}, "
          f"temp G {f['memory'].get('temp_size_in_bytes', 0)/2**30:.2f})")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--calib", action="store_true",
                    help="run the per-period cost calibration instead")
    ap.add_argument("--paper", choices=["d3ca", "radisa"], default=None,
                    help="dry-run the paper's SVM workload instead")
    ap.add_argument("--backend", choices=["ref", "pallas"], default="ref",
                    help="cell-local solver backend for --paper")
    args = ap.parse_args()

    if args.paper:
        run_paper_cell(args.paper, multi_pod=args.multi_pod,
                       local_backend=args.backend)
        return

    if args.all:
        ok = True
        for arch in ARCHS:
            for shape in LM_SHAPES:
                try:
                    if args.calib:
                        run_calibration(arch, shape.name)
                    else:
                        run_cell(arch, shape.name, args.multi_pod)
                except Exception as e:
                    ok = False
                    print(f"[dryrun] {arch} x {shape.name}: FAIL {e!r}",
                          file=sys.stderr)
        sys.exit(0 if ok else 1)

    if args.calib:
        run_calibration(args.arch, args.shape or "train_4k")
    else:
        run_cell(args.arch, args.shape or "train_4k", args.multi_pod)


if __name__ == "__main__":
    main()
