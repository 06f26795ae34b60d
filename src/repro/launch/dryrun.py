import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: .lower().compile() every (architecture x input-shape
x mesh) cell on the production mesh built from 512 placeholder host
devices, and record memory/cost/collective evidence for the roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun
Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json and prints
memory_analysis() + cost_analysis() summaries (the §Dry-run evidence).
"""
import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCH_IDS, ALIASES, get_config  # noqa: E402
from repro.launch.mesh import dp_axes, make_production_mesh  # noqa: E402
from repro.models import (SHAPES, abstract_opt_state, abstract_params,  # noqa: E402
                          input_specs, make_prefill_step, make_serve_step,
                          make_train_step, shape_applicable)
from repro.models import transformer as tfm  # noqa: E402
from repro.sharding import (batch_specs, cache_specs, param_specs,  # noqa: E402
                            to_named)

_COLL_LINE_RE = re.compile(
    r"=\s*(?P<types>\(?[^()=]*?\)?)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<variant>-start)?\(", re.IGNORECASE)

_TYPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "c64": 8,
               "s16": 2, "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output bytes of every collective op in the (post-SPMD) HLO.
    Matches the op CALL (`= <type> all-gather(...)`), not instruction
    names; `-done` ops are skipped (the `-start` already carries the
    buffer) and `-start` tuple outputs are halved (in+out aliases)."""
    totals = {}
    for m in _COLL_LINE_RE.finditer(hlo_text):
        op = m.group("op").lower()
        types = m.group("types")
        b = 0
        for t in _TYPE_RE.finditer(types):
            dt, dims = t.group(1), t.group(2)
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            b += n * DTYPE_BYTES.get(dt, 4)
        if m.group("variant"):
            b //= 2
        totals[op] = totals.get(op, 0) + b
        totals["total"] = totals.get("total", 0) + b
    return totals


def sharded_struct(tree, spec_tree, mesh):
    """Attach NamedShardings to a ShapeDtypeStruct tree."""
    return jax.tree.map(
        lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=NamedSharding(mesh, sp)),
        tree, spec_tree)


def build_cell(arch: str, shape_name: str, mesh, overrides=None):
    """Returns (jitted_fn, example_args (abstract), meta).

    overrides (the §Perf hillclimb knobs):
      attn_q_chunk: int     — chunked attention (no SqxSk scores)
      policy: "tp"|"dp_only" — dp_only replicates params, folds the model
                               axis into data parallelism (small models)
      remat_policy: "full"|"dots"
      capacity_factor: float — MoE EP capacity
    """
    overrides = overrides or {}
    import dataclasses as _dc
    cfg = get_config(arch)
    shape_pre = SHAPES[shape_name]
    # chunked attention by default at 32k+ prefill: removes the SqxSk
    # score materialization (confirmed pure win — §Perf H1)
    if shape_pre.kind == "prefill" and shape_pre.seq_len >= 32768 \
            and not cfg.attn_q_chunk:
        cfg = _dc.replace(cfg, attn_q_chunk=2048)
    if overrides.get("attn_q_chunk"):
        cfg = _dc.replace(cfg, attn_q_chunk=overrides["attn_q_chunk"])
    if overrides.get("capacity_factor"):
        cfg = _dc.replace(cfg,
                          capacity_factor=overrides["capacity_factor"])
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": why}

    dp = dp_axes(mesh)
    dp_only = overrides.get("policy") == "dp_only"
    use_ep = (cfg.n_experts > 0 and shape.kind in ("train", "prefill")
              and not dp_only and not overrides.get("no_ep"))
    fsdp = cfg.param_count() > 8e9 and not dp_only
    remat_policy = overrides.get("remat_policy", "full")
    # sequence-parallel activation constraint — EXCEPT for EP cells:
    # the SP layout fights the EP token layout at the shard_map boundary
    # and the partitioner falls back to replication (measured: kimi-k2
    # multi-pod temp 2154 GiB with SP -> 57 GiB without; §Perf H3)
    act_sharding = None
    if shape.kind in ("train", "prefill") and not dp_only \
            and not use_ep and not overrides.get("no_sp") \
            and shape.seq_len % mesh.shape["model"] == 0:
        act_sharding = NamedSharding(mesh, P(dp, "model", None))

    optimizer = "adafactor" if cfg.param_count() > 3e11 else "adamw"

    aparams = abstract_params(cfg, max_len=shape.seq_len)
    if dp_only:
        pspecs = jax.tree.map(lambda l: P(*([None] * l.ndim)), aparams)
    else:
        pspecs = param_specs(aparams, cfg, mesh, fsdp=fsdp)
    aparams = sharded_struct(aparams, pspecs, mesh)

    batch_axes = (dp + ("model",)) if dp_only else dp
    specs = input_specs(cfg, shape)
    meta = {"arch": arch, "shape": shape_name, "use_ep": use_ep,
            "fsdp": fsdp, "optimizer": optimizer,
            "sequence_parallel": act_sharding is not None,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "overrides": {k: v for k, v in overrides.items()}}

    def batch_specs(tree, mesh):   # shadow: respect dp_only batch axes
        from repro.sharding.rules import with_divisibility

        def assign(path, leaf):
            if leaf.ndim == 0:
                return P()
            spec = P(batch_axes, *([None] * (leaf.ndim - 1)))
            return with_divisibility(spec, leaf.shape, mesh)
        return jax.tree_util.tree_map_with_path(assign, tree)

    if shape.kind == "train":
        step = make_train_step(cfg, mesh=mesh, dp_axes=dp, use_ep=use_ep,
                               act_sharding=act_sharding,
                               optimizer=optimizer,
                               remat_policy=remat_policy,
                               microbatch=overrides.get("microbatch", 1),
                               ep_fsdp=(use_ep and fsdp),
                               accum_dtype=(jnp.bfloat16 if overrides.get(
                                   "accum_bf16") else jnp.float32))
        aopt = abstract_opt_state(aparams, optimizer)
        if dp_only:
            ospecs = jax.tree.map(lambda l: P(*([None] * l.ndim)), aopt)
        elif optimizer == "adafactor":
            from repro.sharding.rules import adafactor_state_specs
            ospecs = adafactor_state_specs(aopt, pspecs, aparams, mesh)
        else:
            ospecs = param_specs(aopt, cfg, mesh, fsdp=fsdp)
        aopt = sharded_struct(aopt, ospecs, mesh)
        batch = {k: v for k, v in specs.items()}
        bspecs = batch_specs(batch, mesh)
        batch = sharded_struct(batch, bspecs, mesh)
        fn = jax.jit(step, donate_argnums=(0, 1))
        return fn, (aparams, aopt, batch), meta

    if shape.kind == "prefill":
        step = make_prefill_step(cfg, mesh=mesh, dp_axes=dp, use_ep=use_ep,
                                 act_sharding=act_sharding,
                                 ep_fsdp=(use_ep and fsdp))
        batch = {k: v for k, v in specs.items()}
        bspecs = batch_specs(batch, mesh)
        batch = sharded_struct(batch, bspecs, mesh)
        fn = jax.jit(step)
        return fn, (aparams, batch), meta

    # decode
    step = make_serve_step(cfg)
    token = specs["token"]
    acache = specs["cache"]
    cspecs = cache_specs(acache, cfg, mesh)
    acache = sharded_struct(acache, cspecs, mesh)
    token = sharded_struct(token, batch_specs(token, mesh), mesh)
    args = [aparams, acache, token]
    if "cross_source" in specs:
        cs = specs["cross_source"]
        args.append(sharded_struct(cs, batch_specs(cs, mesh), mesh))
    fn = jax.jit(step, donate_argnums=(1,))
    return fn, tuple(args), meta


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path,
             force: bool = False, overrides=None, tag_suffix="") -> dict:
    tag = f"{arch}__{shape_name}__{mesh_kind}{tag_suffix}"
    out_file = out_dir / f"{tag}.json"
    if out_file.exists() and not force:
        rec = json.loads(out_file.read_text())
        print(f"[cached] {tag}: {rec.get('status')}")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = {"cell": tag, "mesh": list(mesh.shape.values()),
           "n_devices": mesh.size}
    t0 = time.time()
    try:
        fn, args, meta = build_cell(arch, shape_name, mesh,
                                    overrides=overrides)
        rec.update(meta)
        if fn is None:
            rec["status"] = "skipped"
            out_file.write_text(json.dumps(rec, indent=1))
            print(f"[skip] {tag}: {meta['skipped']}")
            return rec
        lowered = fn.lower(*args)
        rec["lower_s"] = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = time.time() - t1

        mem = compiled.memory_analysis()
        rec["memory"] = {
            k: int(getattr(mem, k, 0)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")}
        cost = compiled.cost_analysis()
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float, np.floating))
                       and k in ("flops", "bytes accessed",
                                 "transcendentals", "optimal_seconds")}
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes(hlo)
        rec["hlo_len"] = len(hlo)
        rec["status"] = "ok"
        print(f"[ok]   {tag}: flops={rec['cost'].get('flops', 0):.3e} "
              f"bytes={rec['cost'].get('bytes accessed', 0):.3e} "
              f"coll={rec['collectives'].get('total', 0):.3e}B "
              f"temp={rec['memory']['temp_size_in_bytes'] / 2**30:.2f}GiB "
              f"({rec['lower_s']:.0f}s lower, {rec['compile_s']:.0f}s "
              f"compile)")
        print(f"       memory_analysis: {rec['memory']}")
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {tag}: {rec['error'].splitlines()[0][:200]}")
    out_file.write_text(json.dumps(rec, indent=1))
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = ARCH_IDS if (args.all or not args.arch) else \
        [ALIASES.get(args.arch, args.arch)]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    n_fail = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, mesh_kind, out_dir,
                               force=args.force)
                n_fail += rec.get("status") == "error"
    print(f"\ndone; {n_fail} failures")
    return 1 if n_fail else 0


if __name__ == "__main__":
    sys.exit(main())
