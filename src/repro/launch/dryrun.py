import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402

from repro.configs import REGISTRY, SHAPES, cell_applicable, get_config, get_shape  # noqa: E402
from repro.launch import roofline as rl  # noqa: E402
from repro.launch import mesh as hw      # noqa: E402
from repro.launch.mesh import make_ctx, make_production_mesh  # noqa: E402
from repro.launch.specs import input_specs  # noqa: E402
from repro.models import get_model       # noqa: E402
from repro.sharding.ctx import DEFAULT_RULES  # noqa: E402

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (arch × shape × mesh) cell:
  jax.jit(step, in_shardings, out_shardings).lower(**input_specs).compile()
then record memory_analysis(), cost_analysis(), the parsed collective
schedule, and the three roofline terms.  Single-pod = (16,16) 256 chips;
multi-pod = (2,16,16) 512 chips with the 'pod' axis as extra data parallel.
"""


# int8 KV cache for decode cells whose bf16 cache exceeds HBM (MHA-32 @
# batch 128 × 32k = 8.6 GiB/chip in bf16; int8 halves it) — §Known-issues
KV_QUANT_DECODE = {"codeqwen1.5-7b"}


def resolve_rules(cfg, shape, rules_name: str, multi_pod: bool = False) -> str:
    """Per-family baseline config ('auto'), set by the §Perf hillclimbs:

    * train, non-MoE, single-pod → pure FSDP (no TP activation collectives;
      batch 256 == 256 chips).  command-r excepted: its 256k-vocab × 8192-d
      head cannot be FSDP-gathered on a 16 GiB chip → 2D rules.
    * train, non-MoE, multi-pod → context parallel (batch 256 < 512 chips,
      so FSDP would leave the model axis idle; cp shards seq over it).
    * MoE train → 2D rules + shard_map combine-before-reduce (§Perf A).
    * prefill (non-encdec) → context parallel (§Perf B/C/E winners: less
      collective traffic and the only layout that fits dbrx/chameleon).
    * decode → 2D rules + tp_seq KV flash-decode.
    """
    if rules_name != "auto":
        return rules_name
    if shape.kind == "train" and cfg.family != "moe":
        if cfg.name == "command-r-35b":
            return "default"   # 256k-vocab head can't be gathered (cp/fsdp)
        return "cp" if multi_pod else "fsdp"
    if shape.kind == "prefill" and cfg.family != "encdec":
        return "cp"
    return "default"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             accum: Optional[int] = None, rules_name: str = "auto",
             seq_shard: bool = True, q_chunk: int = 256,
             remat: bool = True, verbose: bool = True,
             moe_impl: str = "einsum", ssm_chunk: Optional[int] = None,
             loss_chunk: int = 1024) -> Dict:
    import dataclasses
    cfg = get_config(arch)
    if ssm_chunk and cfg.ssm is not None:
        cfg = dataclasses.replace(
            cfg, ssm=dataclasses.replace(cfg.ssm, chunk=ssm_chunk))
    shape = get_shape(shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rules_name = resolve_rules(cfg, shape, rules_name, multi_pod)
    rec: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "rules": rules_name, "accum": accum, "seq_shard": seq_shard,
                 "moe_impl": moe_impl, "ssm_chunk": ssm_chunk,
                 "q_chunk": q_chunk}
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        rec.update(status="SKIP", reason=why)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    if (moe_impl == "einsum" and cfg.family == "moe"
            and shape.kind in ("train", "prefill")):
        moe_impl = "shard_map"          # §Perf A/E default for MoE
        rec["moe_impl"] = moe_impl
    kw = {"moe_impl": moe_impl}
    if rules_name not in ("fsdp", "cp"):
        kw["seq_shard"] = seq_shard
    ctx = make_ctx(mesh, preset=rules_name, **kw)
    if accum is None and rules_name == "fsdp":
        accum = 1  # pure FSDP: batch is 1 seq/chip, microbatching would
        #            degenerate the batch sharding; remat covers memory
    rec["accum"] = accum
    if shape.kind == "long_decode":
        ctx = ctx.replace(rules=dict(ctx.rules, kv_seq="__dp__"),
                          decode_kv="dp_seq")
    elif shape.kind == "decode" and cfg.family != "encdec":
        # big KV caches: shard the cache seq dim over the model axis and
        # LSE-combine (flash-decode) — GQA head counts need not divide TP
        ctx = ctx.replace(rules=dict(ctx.rules, kv_seq="__tp__",
                                     kv_heads=None),
                          decode_kv="tp_seq")
    elif shape.kind == "prefill":
        # produced caches leave prefill in the serving layout
        ctx = ctx.replace(rules=dict(ctx.rules, kv_seq="__tp__",
                                     kv_heads=None))
    if q_chunk == 256 and cfg.d_model >= 8192 and shape.kind == "prefill":
        q_chunk = 64   # cp keeps all heads per chip: bound the f32 score
        rec["q_chunk"] = q_chunk  # buffer at [B,KV,G,64,32768]
    kv_quant = (shape.kind == "decode" and cfg.family != "encdec"
                and cfg.name in KV_QUANT_DECODE)
    rec["kv_quant"] = kv_quant
    mkw = {"kv_quant": kv_quant} if cfg.family != "encdec" else {}
    model = get_model(cfg, ctx, q_chunk=q_chunk, remat=remat,
                      loss_chunk=loss_chunk, **mkw)
    fn, args, in_sh, out_sh, donate = input_specs(
        cfg, shape, model, ctx, accum=accum)

    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    text = compiled.as_text()
    roof = rl.from_compiled(compiled, n_chips=mesh.size,
                            model_flops_total=rl.model_flops(cfg, shape),
                            hlo_text=text)
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    # XLA:CPU legalizes bf16 compute to f32, inflating temp buffers ~2× vs
    # the TPU target; arguments keep their declared dtypes.  TPU estimate:
    peak_tpu = (mem.argument_size_in_bytes + mem.temp_size_in_bytes // 2
                + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec.update(
        status="OK",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": peak,
            "peak_bytes_tpu_est": peak_tpu,
            "fits_hbm": bool(peak_tpu <= hw.HBM_BYTES),
        },
        roofline=roof.to_dict(),
    )
    if verbose:
        print(f"[{arch} × {shape_name} × {mesh_name}] "
              f"lower {t_lower:.1f}s compile {t_compile:.1f}s "
              f"peak {peak_tpu/2**30:.2f} GiB (TPU est) "
              f"fits={peak_tpu <= hw.HBM_BYTES} "
              f"dominant={roof.dominant} step={roof.step_s*1e3:.2f} ms "
              f"mfu_bound={roof.model_flops_utilization:.3f}")
        print("  memory_analysis:", mem)
        ca = compiled.cost_analysis()
        print("  cost_analysis: flops=%.3e bytes=%.3e" %
              (ca.get("flops", 0.0), ca.get("bytes accessed", 0.0)))
        print("  collectives:", roof.collectives.bytes_by_kind)
    return rec


def iter_cells(archs, shapes, meshes):
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                yield arch, shape_name, multi_pod


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="run every cell")
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--rules", default="auto",
                    choices=["auto", "default", "fsdp", "ep", "cp"])
    ap.add_argument("--moe-impl", default="einsum",
                    choices=["einsum", "shard_map"])
    ap.add_argument("--ssm-chunk", type=int, default=None)
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=256)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells already in --out")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(REGISTRY)
    shapes = [args.shape] if args.shape else [s.name for s in SHAPES]
    if args.multi_pod and not args.single_pod:
        meshes = [True]
    elif args.single_pod and not args.multi_pod:
        meshes = [False]
    else:
        meshes = [False, True]

    done = set()
    if args.resume and args.out and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("OK", "SKIP"):
                        done.add((r["arch"], r["shape"], r["mesh"],
                                  r.get("rules", "default")))
                except Exception:
                    pass

    n_ok = n_skip = n_fail = 0
    for arch, shape_name, multi_pod in iter_cells(archs, shapes, meshes):
        mesh_name = "2x16x16" if multi_pod else "16x16"
        resolved = resolve_rules(get_config(arch), get_shape(shape_name),
                                 args.rules, multi_pod)
        if (arch, shape_name, mesh_name, resolved) in done:
            continue
        try:
            rec = run_cell(arch, shape_name, multi_pod=multi_pod,
                           accum=args.accum, rules_name=args.rules,
                           seq_shard=not args.no_seq_shard,
                           q_chunk=args.q_chunk, remat=not args.no_remat,
                           moe_impl=args.moe_impl, ssm_chunk=args.ssm_chunk)
            n_ok += rec["status"] == "OK"
            n_skip += rec["status"] == "SKIP"
            if rec["status"] == "SKIP":
                print(f"[{arch} × {shape_name} × {mesh_name}] SKIP: {rec['reason']}")
        except Exception as e:  # a failed cell is a bug in our sharding
            n_fail += 1
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "rules": args.rules, "status": "FAIL",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[{arch} × {shape_name} × {mesh_name}] FAIL: {e}")
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
    print(f"done: {n_ok} ok, {n_skip} skip, {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
