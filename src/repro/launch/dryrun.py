import os
# A host-device tool: pinned to the CPU, for itself and the per-cell child
# processes it starts (they inherit this environment), so it never holds an
# accelerator while its children start.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
partitions, and compiles, and extract the roofline inputs.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod or 2x16x16 multi-pod),
  2. lowers + compiles the cell's step function against ShapeDtypeStruct
     inputs (no allocation),
  3. records ``compiled.memory_analysis()`` (proves it fits),
     ``compiled.cost_analysis()`` (FLOPs/bytes for the roofline), and the
     per-device collective bytes parsed from the post-SPMD HLO,
  4. writes one JSON per cell under --out (default experiments/dryrun/).

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all            # every applicable cell,
                                                 # both meshes, subprocess
                                                 # isolation per cell
"""
import argparse
import json
import subprocess
import sys
import time
import traceback


def run_cell(arch: str, shape: str, mesh_kind: str, quant: str,
             out_dir: str, prequant: bool = False) -> dict:
    import jax
    from repro.configs import SHAPES, cell_applicable, get_config
    from repro.launch import steps
    from repro.launch.hlo_stats import collective_bytes
    from repro.launch.mesh import make_production_mesh
    from repro.train import optim

    cfg = get_config(arch, quant=quant)
    cell = SHAPES[shape]
    qlabel = quant + ("+pq" if prequant else "")
    record = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "quant": qlabel,
        "n_devices": 512 if mesh_kind == "multi" else 256,
    }
    if not cell_applicable(cfg, shape):
        record.update(status="skipped",
                      reason="long_500k requires sub-quadratic decode "
                             "(see DESIGN.md §6)")
        return record
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    ocfg = optim.AdamWConfig()
    specs = steps.input_specs(cfg, cell, mesh, ocfg, prequant=prequant)
    if cell.kind == "train":
        fn = steps.make_train_step(cfg, ocfg)
        args = (specs["params"], specs["opt_state"], specs["batch"])
        donate = (0, 1)
    elif cell.kind == "prefill":
        fn = steps.make_prefill_step(cfg)
        args = (specs["params"], specs["cache"], specs["batch"])
        donate = (1,)
    else:
        fn = steps.make_decode_step(cfg)
        args = (specs["params"], specs["cache"], specs["token"], specs["t"])
        if "mem" in specs:
            args = args + (specs["mem"],)
        donate = (1,)

    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    try:
        mem = compiled.memory_analysis()
    except Exception:   # some backends lack the C++ API; keep going
        mem = None
    try:
        cost = compiled.cost_analysis() or {}
    except Exception:
        cost = {}
    hlo = compiled.as_text()
    # Trip-count-aware walk (XLA's cost_analysis counts while bodies once —
    # see hlo_stats; the raw numbers are kept for reference as cost_xla).
    from repro.launch.hlo_stats import parse_costs
    full = parse_costs(hlo)
    _save_hlo(out_dir, arch, shape, mesh_kind, qlabel, hlo)
    record.update(
        status="ok",
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        cost={"flops": full.get("flops", 0.0),
              "bytes accessed": full.get("bytes", 0.0)},
        cost_xla={k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float)) and k in
                  ("flops", "bytes accessed")},
        memory={
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes",
                                            None),
        },
        collectives={k: v for k, v in full.items()
                     if k.endswith("_bytes") or k.endswith("_count")},
        hlo_lines=hlo.count("\n"),
    )
    return record


def _save_hlo(out_dir, arch, shape, mesh_kind, quant, hlo: str) -> None:
    """Keep the post-SPMD HLO (zstd) so costs can be re-derived offline."""
    try:
        import zstandard as zstd

        path = _out_path(out_dir, arch, shape, mesh_kind, quant).replace(
            ".json", ".hlo.zst")
        with open(path, "wb") as f:
            f.write(zstd.ZstdCompressor(level=6).compress(hlo.encode()))
    except Exception:
        pass


def _out_path(out_dir, arch, shape, mesh_kind, quant):
    safe = arch.replace(".", "_")
    return os.path.join(out_dir, f"{safe}__{shape}__{mesh_kind}__{quant}.json")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--quant", default="w12")
    ap.add_argument("--prequant", action="store_true",
                    help="serve cells use pre-quantized weight storage")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells that already have a JSON")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        from repro.configs import SHAPES, list_archs
        failures = 0
        for arch in list_archs():
            for shape in SHAPES:
                for mesh_kind in ("single", "multi"):
                    path = _out_path(args.out, arch, shape, mesh_kind,
                                     args.quant)
                    if os.path.exists(path) and not args.force:
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--mesh", mesh_kind, "--quant", args.quant,
                           "--out", args.out]
                    print(f"[dryrun] {arch} x {shape} x {mesh_kind}",
                          flush=True)
                    r = subprocess.run(cmd)
                    if r.returncode:
                        failures += 1
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape required"
    qlabel = args.quant + ("+pq" if args.prequant else "")
    path = _out_path(args.out, args.arch, args.shape, args.mesh, qlabel)
    try:
        record = run_cell(args.arch, args.shape, args.mesh, args.quant,
                          args.out, prequant=args.prequant)
    except Exception as e:  # record the failure — it is a bug to fix
        record = {
            "arch": args.arch, "shape": args.shape, "mesh": args.mesh,
            "quant": args.quant, "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    print(json.dumps({k: v for k, v in record.items()
                      if k not in ("traceback",)}, indent=1))
    return 0 if record.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
