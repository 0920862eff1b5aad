import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell with ShapeDtypeStruct stand-ins (no allocation), print
memory_analysis / cost_analysis, and write roofline artifacts.

The two lines above MUST stay first: jax locks the device count on
first initialization.  Everything jax-related is imported after.

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k
  python -m repro.launch.dryrun --all                  # 40-cell sweep
  python -m repro.launch.dryrun --all --multi-pod      # 512-chip mesh
  python -m repro.launch.dryrun --all --roofline       # + unrolled variants
  python -m repro.launch.dryrun --arch hdc_mnist       # the paper's system

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and are
consumed by benchmarks/roofline.py and EXPERIMENTS.md.
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.analysis import roofline
from repro.configs import ARCHS, get_config
from repro.distributed.sharding import set_current_mesh
from repro.launch.mesh import describe, make_production_mesh
from repro.launch.specs import input_specs
from repro.models import transformer
from repro.models.config import LONG_CONTEXT_OK, SHAPES
from repro.optim import OptimizerConfig
from repro.training.step import make_train_step

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _lower(cfg, shape, inputs):
    """Lower the right step function for the shape kind."""
    if shape.kind == "train":
        step_fn = make_train_step(cfg, OptimizerConfig())
        f = jax.jit(step_fn, donate_argnums=(0, 1))
        return f.lower(
            inputs["params"], inputs["opt_state"], inputs["batch"], inputs["step"]
        )
    if shape.kind == "prefill":
        f = jax.jit(lambda p, b: transformer.prefill(cfg, p, b))
        return f.lower(inputs["params"], inputs["batch"])
    if cfg.input_mode == "embeddings":
        f = jax.jit(
            lambda p, s, t, e: transformer.decode_step(cfg, p, s, t, embeddings=e),
            donate_argnums=(1,),
        )
        return f.lower(
            inputs["params"], inputs["state"], inputs["tokens"], inputs["embeddings"]
        )
    f = jax.jit(
        lambda p, s, t: transformer.decode_step(cfg, p, s, t), donate_argnums=(1,)
    )
    return f.lower(inputs["params"], inputs["state"], inputs["tokens"])


def _cell_stats(compiled) -> dict:
    ca = compiled.cost_analysis()
    coll = roofline.collective_bytes(compiled.as_text())
    counts = coll.pop("_counts")
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(sum(coll.values())),
        "coll_by_type": coll,
        "coll_counts": counts,
    }


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    return {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_bytes_est": ma.argument_size_in_bytes
        + ma.output_size_in_bytes
        + ma.temp_size_in_bytes
        - ma.alias_size_in_bytes,
    }


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    do_roofline: bool = False,
    verbose: bool = True,
    overrides: dict | None = None,
) -> dict:
    """Lower+compile one cell; optionally derive loop-corrected roofline.

    `overrides` patches the registered config (perf-iteration variants).
    """
    from repro.launch.specs import input_specs_for

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "multi" if multi_pod else "single"
    n_chips = mesh.devices.size
    set_current_mesh(mesh)
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": n_chips,
        "overrides": overrides or {},
    }

    base_cfg = get_config(arch)
    if overrides:
        base_cfg = dataclasses.replace(base_cfg, **overrides)
    cfg, shape, rules, inputs = input_specs_for(base_cfg, shape_name, mesh)
    t0 = time.time()
    with mesh:
        lowered = _lower(cfg, shape, inputs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    record["lower_s"] = round(t_lower, 2)
    record["compile_s"] = round(t_compile, 2)
    record["memory"] = _memory(compiled)
    record["raw"] = _cell_stats(compiled)

    if verbose:
        mem = record["memory"]
        print(
            f"  [{describe(mesh)}] lower {t_lower:.1f}s compile {t_compile:.1f}s | "
            f"args {mem['argument_bytes']/2**30:.2f} GiB  temp "
            f"{mem['temp_bytes']/2**30:.2f} GiB  peak~{mem['peak_bytes_est']/2**30:.2f} GiB"
        )

    if do_roofline and not multi_pod:
        # unrolled variants for loop-corrected FLOP/byte accounting
        period, tail_len = cfg.period, len(cfg.tail_pattern)

        def unrolled(n_layers):
            # grad_accum=1: the microbatch sweep multiplies HLO size but
            # not total cost (accum x (B/accum) == B), so the unrolled
            # cost-extraction variants lower it away for compile speed.
            from repro.launch.specs import input_specs_for

            ucfg = dataclasses.replace(
                cfg, n_layers=n_layers, scan_layers=False, unroll_loops=True,
                grad_accum=1,
            )
            _, _, _, uin = input_specs_for(ucfg, shape_name, mesh)
            with mesh:
                c = _lower(ucfg, shape, uin).compile()
            return _cell_stats(c)

        t0 = time.time()
        u1 = unrolled(period)
        u2 = unrolled(2 * period)
        tail = unrolled(period + tail_len) if tail_len else None
        corrected = roofline.combine_unrolled(u1, u2, cfg.n_groups, tail, record["raw"])
        record["corrected"] = corrected
        record["roofline_s"] = round(time.time() - t0, 2)

        terms = roofline.RooflineTerms(
            corrected["flops"], corrected["bytes"], corrected["coll_bytes"]
        )
        mf = roofline.model_flops(cfg, shape, n_chips)
        hlo_global = corrected["flops"] * n_chips
        record["terms"] = terms.asdict()
        record["model_flops"] = mf
        record["useful_flops_ratio"] = mf / hlo_global if hlo_global else 0.0
        if verbose:
            print(
                f"  roofline: compute {terms.compute_s*1e3:.2f} ms | memory "
                f"{terms.memory_s*1e3:.2f} ms | collective {terms.collective_s*1e3:.2f} ms "
                f"-> {terms.dominant}-bound; useful/HLO flops = "
                f"{record['useful_flops_ratio']:.2f}"
            )
    return record


def run_hdc(multi_pod: bool = False, d: int = 8192, verbose: bool = True) -> dict:
    """Dry-run the paper's own system at scale: uHD single-pass fit over a
    globally sharded image batch (65536 images x 784 features)."""
    from repro.core import HDCConfig, HDCModel, hdc_model

    mesh = make_production_mesh(multi_pod=multi_pod)
    set_current_mesh(mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = HDCConfig(n_features=784, n_classes=16, d=d, backend="unary_matmul")
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    images = jax.ShapeDtypeStruct(
        (65536, 784), jnp.float32, sharding=NamedSharding(mesh, P(batch_axes, None))
    )
    labels = jax.ShapeDtypeStruct(
        (65536,), jnp.int32, sharding=NamedSharding(mesh, P(batch_axes))
    )
    sobol = jax.ShapeDtypeStruct(
        (784, d), jnp.int32, sharding=NamedSharding(mesh, P(None, "model"))
    )
    model = HDCModel.from_parts(cfg, {"sobol": sobol})
    t0 = time.time()
    with mesh:
        lowered = hdc_model.fit.lower(model, images, labels)
        compiled = lowered.compile()
    rec = {
        "arch": "hdc_mnist", "shape": f"fit_65536xD{d}",
        "mesh": "multi" if multi_pod else "single",
        "chips": mesh.devices.size,
        "compile_s": round(time.time() - t0, 2),
        "memory": _memory(compiled),
        "raw": _cell_stats(compiled),
    }
    if verbose:
        t = roofline.RooflineTerms(
            rec["raw"]["flops"], rec["raw"]["bytes"], rec["raw"]["coll_bytes"]
        )
        print(
            f"  hdc fit [{describe(mesh)}]: compile {rec['compile_s']}s | compute "
            f"{t.compute_s*1e6:.1f} us | memory {t.memory_s*1e6:.1f} us | "
            f"collective {t.collective_s*1e6:.1f} us -> {t.dominant}-bound"
        )
    return rec


def cells(include_skips: bool = True):
    for arch in ARCHS:
        for shape_name in SHAPES:
            skip = shape_name == "long_500k" and arch not in LONG_CONTEXT_OK
            if skip and not include_skips:
                continue
            yield arch, shape_name, skip


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    todo: list[tuple[str, str, bool]] = []
    if args.arch == "hdc_mnist":
        for mp in meshes:
            rec = run_hdc(multi_pod=mp)
            path = out_dir / f"hdc_mnist__fit__{rec['mesh']}.json"
            path.write_text(json.dumps(rec, indent=1))
        return 0
    if args.all:
        todo = list(cells())
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        skip = args.shape == "long_500k" and args.arch not in LONG_CONTEXT_OK
        todo = [(args.arch, args.shape, skip)]

    failures = 0
    for arch, shape_name, skip in todo:
        for mp in meshes:
            mesh_name = "multi" if mp else "single"
            tag = f"{arch} x {shape_name} [{mesh_name}]"
            path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
            if args.skip_existing and path.exists():
                rec = json.loads(path.read_text())
                if "skipped" in rec or "memory" in rec and (
                    not args.roofline or mp or "terms" in rec
                ):
                    print(f"SKIP (exists) {tag}")
                    continue
            if skip:
                print(f"SKIP {tag}: long_500k needs sub-quadratic attention "
                      f"(pure full-attention arch; see DESIGN.md)")
                path.write_text(json.dumps({
                    "arch": arch, "shape": shape_name, "mesh": mesh_name,
                    "skipped": "full-attention arch at 500k context",
                }, indent=1))
                continue
            print(f"RUN  {tag}")
            try:
                rec = run_cell(
                    arch, shape_name, multi_pod=mp, do_roofline=args.roofline
                )
                path.write_text(json.dumps(rec, indent=1))
            except Exception:
                failures += 1
                print(f"FAIL {tag}")
                traceback.print_exc()
    print(f"\ndone; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
