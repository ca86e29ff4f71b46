"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: builds the
production mesh from 512 placeholder host devices (``jax.devices("cpu")``,
so the census runs the same on a machine that also holds a TPU), lowers the
real train / serve step with ShapeDtypeStruct inputs (no allocation),
compiles, and records memory_analysis / cost_analysis / the collective
census (HxA) to a JSON artifact per cell under ``experiments/dryrun/``.

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod]
"""

# MUST be the very first lines — jax locks the device count on first init.
import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The TPU runtime pins a host staging buffer as it starts: 4 GiB by default,
# which took 7-9.5 s of each process's start on one v5e VM.  This program's
# host-device copies are tens of MB at most; a caller's own setting wins.
os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs.base import ARCH_NAMES, SHAPES, get_config  # noqa: E402
from repro.launch.cache import enable_compile_cache            # noqa: E402
from repro.launch.mesh import make_production_mesh             # noqa: E402
from repro.launch.lowering import lower_cell                   # noqa: E402


def art_dir() -> str:
    d = os.environ.get("REPRO_ART_DIR",
                       os.path.abspath(os.path.join(os.getcwd(), "experiments", "dryrun")))
    os.makedirs(d, exist_ok=True)
    return d


def run_cell(arch: str, shape_name: str, multi_pod: bool, save: bool = True,
             overrides: dict | None = None, out_dir: str | None = None) -> dict:
    """Census one cell; with ``save`` the artifact goes to ``out_dir``
    (default ``art_dir()``)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    save_hlo = os.environ.get("REPRO_SAVE_HLO", "1") == "1"
    result = lower_cell(cfg, shape, mesh, overrides=overrides or {},
                        include_hlo=save_hlo)
    result["wall_s"] = round(time.time() - t0, 2)
    result["arch"] = arch
    result["shape"] = shape_name
    result["mesh"] = "2x16x16" if multi_pod else "16x16"
    if save:
        tag = f"{arch}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
        if overrides:
            tag += "__" + "_".join(f"{k}-{v}" for k, v in sorted(overrides.items()))
        out_dir = out_dir or art_dir()
        hlo_text = result.pop("hlo_text", None)
        if hlo_text is not None:
            import gzip
            hdir = os.path.join(out_dir, "hlo")
            os.makedirs(hdir, exist_ok=True)
            with gzip.open(os.path.join(hdir, tag + ".hlo.gz"), "wt") as f:
                f.write(hlo_text)
        path = os.path.join(out_dir, tag + ".json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
        print(f"[dryrun] wrote {path}")
    return result


def reanalyze(tag: str) -> dict:
    """Rebuild a cell artifact from its stored HLO (analyzer iterations
    without recompiling)."""
    import gzip
    from repro.core import costmodel, hxa
    from repro.hw import get_chip
    from repro.launch.lowering import kernel_substitution
    import dataclasses as _dc
    path = os.path.join(art_dir(), tag + ".json")
    with open(path) as f:
        art = json.load(f)
    with gzip.open(os.path.join(art_dir(), "hlo", tag + ".hlo.gz"), "rt") as f:
        text = f.read()
    mesh_shape = tuple(int(d) for d in art["mesh"].split("x"))
    axes = ("pod", "data", "model")[-len(mesh_shape):]
    analysis = hxa.analyze_hlo_text(text, mesh=dict(zip(axes, mesh_shape)))
    analysis["hbm_bytes_xla"] = analysis["hbm_bytes"]
    cfg_d = art["config"]
    from repro.configs.base import get_config as _gc
    cfg = _gc(art["arch"])
    over = {k: cfg_d[k] for k in ("attn_impl", "ssm_impl", "remat")
            if cfg_d.get(k) is not None}
    cfg = _dc.replace(cfg, **over)
    shape = SHAPES[art["shape"]]
    n_chips = art["roofline"]["n_chips"]
    subst = kernel_substitution(cfg, shape, n_chips, 16)
    saved = subst["attn_bytes_saved_pd"] + subst["ssm_bytes_saved_pd"]
    if saved:
        analysis["hbm_bytes"] = max(analysis["hbm_bytes"] - saved,
                                    analysis["hbm_bytes"] * 0.05)
    analysis["kernel_substitution"] = subst
    chip = get_chip()
    art["hxa"] = {k: analysis[k] for k in
                  ("flops", "hbm_bytes", "hbm_bytes_xla", "collective_bytes",
                   "wire_bytes", "wire_bytes_by_axis", "op_counts",
                   "hbm_by_opcode", "collectives", "loops", "n_computations",
                   "kernel_substitution")}
    art["roofline"] = costmodel.roofline_terms(analysis, chip, n_chips)
    art["sim"] = costmodel.simulate(analysis, chip, n_chips,
                                    mesh=mesh_shape).as_dict()
    hlo_flops_global = analysis["flops"] * n_chips
    art["useful_flops_ratio"] = (art["model_flops"] / hlo_flops_global
                                 if hlo_flops_global else 0.0)
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    return art


def applicable_cells():
    for arch in ARCH_NAMES:
        if arch == "resnet50":
            continue  # paper's own domain: separate bench, not an LM cell
        cfg = get_config(arch)
        for shape in cfg.applicable_shapes():
            yield arch, shape.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--set", action="append", default=[],
                    help="override key=value (e.g. remat=none)")
    args = ap.parse_args()
    overrides = dict(kv.split("=", 1) for kv in args.set)
    enable_compile_cache()

    cells = list(applicable_cells()) if args.all else [(args.arch, args.shape)]
    failures = []
    for arch, shape in cells:
        try:
            r = run_cell(arch, shape, args.multi_pod, overrides=overrides)
            print(f"[dryrun] {arch} x {shape} x {r['mesh']}: "
                  f"state/dev {r['memory']['state_gb_per_device']:.2f} GB, "
                  f"hxa-flops/dev {r['hxa']['flops']:.3e}, "
                  f"dominant {r['roofline']['dominant']}, wall {r['wall_s']}s")
        except Exception as e:
            failures.append((arch, shape, repr(e)))
            traceback.print_exc()
    if failures:
        print("FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
