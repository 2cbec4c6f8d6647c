import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e) + roofline extraction (deliverable g).

For one (arch, shape) pair this script:
  1. builds the production mesh — 16x16 single pod, or 2x16x16 with
     --multi-pod — with 512 placeholder host devices (flags above MUST
     precede any jax import: jax locks the device count on first init);
  2. lowers + compiles the entry point (train_step / prefill_step /
     serve_step) with the DisaggConfig shardings — ShapeDtypeStructs only,
     nothing is allocated;
  3. prints memory_analysis() (fits-or-not per chip) and cost_analysis();
  4. for --mode cost, re-lowers the *unrolled* variant for exact HLO
     FLOP/byte totals and parses per-device collective bytes from the
     post-SPMD module (see launch/hlo_analysis.py);
  5. writes a JSON record under experiments/dryrun/ that launch/roofline.py
     aggregates into EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape decode_32k [--multi-pod] [--mode natural|cost|both]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import sys
import time
import traceback


def run_one(arch: str, shape: str, *, multi_pod: bool, mode: str,
            out_dir: str, attention_partition: str = "auto",
            overrides=None, tag: str = "") -> dict:
    import jax
    from repro.configs import registry
    from repro.launch import analytic, hlo_analysis
    from repro.launch.entrypoints import build_lowering_spec
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    record = {"arch": arch, "shape": shape, "multi_pod": multi_pod,
              "chips": chips, "mode": mode, "tag": tag,
              "attention_partition": attention_partition,
              "overrides": overrides or {}}
    t0 = time.time()

    def lower_compile(unrolled: bool):
        spec = build_lowering_spec(arch, shape, mesh, unrolled=unrolled,
                                   overrides=overrides,
                                   attention_partition=attention_partition)
        jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings,
                         donate_argnums=spec.donate)
        lowered = jitted.lower(*spec.args)
        compiled = lowered.compile()
        return spec, lowered, compiled

    # --- natural (scan) lowering: compile proof + memory analysis ---
    if mode in ("natural", "both"):
        spec, lowered, compiled = lower_compile(unrolled=False)
        mem = compiled.memory_analysis()
        record["entry"] = spec.name
        record["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        }
        per_chip = sum(v for v in [record["memory"]["argument_bytes"],
                                   record["memory"]["temp_bytes"]]
                       if v is not None)
        record["memory"]["per_chip_total"] = per_chip
        record["memory"]["fits_v5e_16g"] = bool(per_chip <= 16 * (1 << 30))
        ca = compiled.cost_analysis()
        record["cost_natural"] = {"flops": ca.get("flops"),
                                  "bytes": ca.get("bytes accessed")}
        coll = hlo_analysis.collective_bytes(compiled.as_text())
        record["collectives_natural"] = coll
        record["compile_s_natural"] = time.time() - t0

    # --- unrolled lowering: exact HLO cost + collective bytes ---
    # Large stacks (gemma2-27b, kimi-k2 train) use two-point layer
    # extrapolation: lower u and 2u layers unrolled, extend linearly in L
    # (exact for layer-uniform programs; embedding/head live in the base
    # term). Chosen automatically above `extrapolate_threshold` layers.
    if mode in ("cost", "both") and not multi_pod:
        t1 = time.time()
        cfg0 = registry.config_for_shape(arch, shape)
        unit = 2 if cfg0.local_global else (
            cfg0.shared_attn_period if cfg0.family == "hybrid" else 1)
        heavy = cfg0.num_layers * max(cfg0.d_model, 1) >= 40 * 4096 or \
            cfg0.num_experts >= 128 or \
            cfg0.family in ("ssm", "hybrid")  # time-scan per layer: costly

        if heavy and cfg0.num_layers > 4 * unit:
            L = cfg0.num_layers

            def cost_at(n_layers):
                ov = dict(overrides or {})
                ov["num_layers"] = n_layers
                if cfg0.family == "audio":
                    ov["encoder_layers"] = n_layers
                sp = build_lowering_spec(
                    arch, shape, mesh, unrolled=True, overrides=ov,
                    attention_partition=attention_partition)
                jt = jax.jit(sp.fn, in_shardings=sp.in_shardings,
                             out_shardings=sp.out_shardings,
                             donate_argnums=sp.donate)
                comp = jt.lower(*sp.args).compile()
                c = comp.cost_analysis()
                cb = hlo_analysis.collective_bytes(comp.as_text())
                return (float(c.get("flops", 0.0)),
                        float(c.get("bytes accessed", 0.0)), cb, sp)

            f1, b1, cb1, _ = cost_at(unit)
            f2, b2, cb2, spec = cost_at(2 * unit)
            k = (L - unit) / unit  # extra units beyond the base lowering
            ca = {"flops": f1 + (f2 - f1) * k,
                  "bytes accessed": b1 + (b2 - b1) * k}
            coll = {kk: cb1[kk] + (cb2[kk] - cb1[kk]) * k
                    for kk in cb1}
            record["cost_method"] = f"extrapolated_u{unit}"
        else:
            spec, lowered, compiled = lower_compile(unrolled=True)
            ca = compiled.cost_analysis()
            coll = hlo_analysis.collective_bytes(compiled.as_text())
            record["cost_method"] = "unrolled_full"
        # corrections always use the FULL layer count
        corr = analytic.recurrence_corrections(cfg0, shape)
        # HLO numbers are per-chip (post-SPMD module); corrections are global
        flops = float(ca.get("flops", 0.0)) + corr["flops"] / chips
        hbm = float(ca.get("bytes accessed", 0.0)) + corr["bytes"] / chips
        mf = analytic.model_flops(spec.cfg, shape)
        terms = hlo_analysis.RooflineTerms(
            flops=flops, hbm_bytes=hbm,
            coll_bytes_per_chip=coll["total"], chips=chips, model_flops=mf)
        record["entry"] = spec.name
        record["cost"] = {"flops_hlo": float(ca.get("flops", 0.0)),
                          "bytes_hlo": float(ca.get("bytes accessed", 0.0)),
                          "flops_correction": corr["flops"],
                          "bytes_correction": corr["bytes"]}
        record["collectives"] = coll
        record["roofline"] = terms.as_dict()
        record["compile_s_cost"] = time.time() - t1

    record["ok"] = True
    record["total_s"] = time.time() - t0
    os.makedirs(out_dir, exist_ok=True)
    suffix = "pod2" if multi_pod else "pod1"
    if tag:
        suffix += f"_{tag}"
    path = os.path.join(out_dir, f"{arch}_{shape}_{suffix}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="both",
                    choices=["natural", "cost", "both"])
    ap.add_argument("--attention-partition", default="auto",
                    choices=["auto", "head", "seq"])
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg overrides k=v (int/float parsed)")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    overrides = {}
    for kv in args.override:
        k, v = kv.split("=", 1)
        try:
            overrides[k] = int(v)
        except ValueError:
            try:
                overrides[k] = float(v)
            except ValueError:
                overrides[k] = v

    from repro.configs import registry

    combos = []
    if args.all:
        for arch in registry.ASSIGNED:
            for shape in registry.applicable_shapes(arch):
                combos.append((arch, shape))
    else:
        combos.append((args.arch, args.shape))

    failures = 0
    for arch, shape in combos:
        try:
            rec = run_one(arch, shape, multi_pod=args.multi_pod,
                          mode=args.mode, out_dir=args.out_dir,
                          attention_partition=args.attention_partition,
                          overrides=overrides or None, tag=args.tag)
            r = rec.get("roofline", {})
            mem = rec.get("memory", {})
            print(f"OK  {arch:24s} {shape:12s} chips={rec['chips']} "
                  f"mem/chip={mem.get('per_chip_total', 0)/(1<<30):.2f}GiB "
                  f"dominant={r.get('dominant', '-')} "
                  f"[{rec['total_s']:.0f}s]")
        except Exception:
            failures += 1
            print(f"FAIL {arch} {shape}", file=sys.stderr)
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
