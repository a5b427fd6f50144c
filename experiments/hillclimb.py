"""Perf hillclimb driver (§Perf): re-run selected (arch × shape) pairs
with candidate optimizations and record tagged dry-run JSONs next to the
baseline for before/after comparison.

  PYTHONPATH=src python experiments/hillclimb.py --pair gemma3-1b:train_4k \
      --policy attn_heads_only --tag hc1
"""
import argparse
import json
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", required=True, help="arch:shape")
    ap.add_argument("--policy", default="baseline")
    ap.add_argument("--tag", required=True)
    ap.add_argument("--fsdp", action="store_true", default=None)
    ap.add_argument("--no-fsdp", dest="fsdp", action="store_false")
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--remat", default=None, choices=("on", "off"))
    ap.add_argument("--act-shard", action="store_true",
                    help="with_sharding_constraint on the scan carry")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--mesh-shape", default=None)
    args = ap.parse_args()

    from repro.launch.dryrun import dryrun_one
    from repro.models.model import ModelOpts

    arch, shape = args.pair.split(":")
    opts = None
    if args.remat is not None or args.act_shard:
        axes = ("data",) if args.act_shard else ()
        opts = ModelOpts(dtype=args.dtype,
                         remat=(args.remat or "on") == "on",
                         act_batch_axes=axes)
    mesh_shape = (tuple(int(x) for x in args.mesh_shape.split(","))
                  if args.mesh_shape else None)
    rec = dryrun_one(arch, shape, param_dtype=args.param_dtype,
                     fsdp=args.fsdp, model_opts=opts, tag=args.tag,
                     policy=args.policy, mesh_shape=mesh_shape)
    keys = ("status", "compile_s", "compute_term_s", "memory_term_s",
            "collective_term_s", "bottleneck", "collective_bytes_corrected",
            "error")
    print(json.dumps({k: rec.get(k) for k in keys}, indent=1))


if __name__ == "__main__":
    main()
