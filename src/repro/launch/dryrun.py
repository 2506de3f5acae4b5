import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_14b \
        --shape train_4k --mesh pod           # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]

Per cell: jit(step).lower(**ShapeDtypeStructs).compile() on the production
mesh; prints memory_analysis() (proves it fits) and cost_analysis() (FLOPs /
bytes for §Roofline); writes experiments/dryrun/<cell>.json.
"""

import argparse
import dataclasses
import json
import pathlib
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.registry import ShapeCell
from repro.launch import mesh as meshlib
from repro.launch import specs as speclib
from repro.launch import steps as steplib
from repro.launch import roofline as rooflib
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig

ART_DIR = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

FSDP_THRESHOLD = 8e9     # params; above this, shard "embed" over data axis


def _batch_shardings(batch_specs, mesh, cfg, dp_axes=("pod", "data")):
    """Activations: batch dim over dp_axes; caches per logical role."""
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)

    def spec_for(path_leaf, sds):
        nd = len(sds.shape)
        if nd == 0:
            return P()
        # batch-major arrays: tokens/labels/pos/ctx/cache leaves all carry
        # batch on dim 0 (cache group leaves carry it on dim 1 after the
        # group-stack axis).
        return P(dp if sds.shape[0] % _prod(mesh, dp) == 0 else None)

    def _prod(mesh, axes):
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        n = 1
        for a in axes:
            n *= sizes[a]
        return n

    def one(path, sds):
        nd = len(sds.shape)
        dpn = _prod(mesh, dp)
        if nd == 0:
            return NamedSharding(mesh, P())
        # batch axis: dim 0 for plain inputs, dim 1 for group-stacked
        # cache leaves ((n_groups, B, ...)).
        dims = [None] * nd
        if sds.shape[0] % dpn == 0 and sds.shape[0] > 1:
            dims[0] = dp if len(dp) > 1 else dp[0]
        elif nd >= 2 and sds.shape[1] % dpn == 0 and sds.shape[1] > 1:
            dims[1] = dp if len(dp) > 1 else dp[0]
        # model-shard the trailing feature dim of 3D+ leaves (KV caches,
        # SSM states, ctx embeddings) — this is what lets a 32k x 128-seq
        # command-r cache fit: (B/dp, L, KV, hd/model).
        msize = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
        if nd >= 3 and dims[-1] is None and sds.shape[-1] % msize == 0 \
                and sds.shape[-1] >= msize:
            dims[-1] = "model"
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map_with_path(one, batch_specs)


def run_cell(arch: str, cell: ShapeCell, multi_pod: bool,
             out_dir: pathlib.Path = ART_DIR, verbose: bool = True,
             overrides=None, sharding: str = "tp", tag: str = ""):
    """sharding: 'tp' (default TP-over-model [+FSDP >= 8B]) or 'fsdp_dp'
    (§Perf: batch over ALL axes, params ZeRO-3 over 'data', no TP — the
    small-model layout that removes per-layer TP all-reduces)."""
    t0 = time.time()
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    cfg = registry.get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    pshapes, paxes = speclib.param_specs(cfg)
    import math as _math
    n_params = sum(_math.prod(s.shape) for s in jax.tree.leaves(pshapes))
    opt_rules = None
    if sharding == "fsdp_dp":
        rules = {k: None for k in meshlib.BASE_RULES}
        rules["embed"] = "data"
        rules["batch"] = ("pod", "data", "model")
        dp_axes = ("pod", "data", "model")
    elif sharding == "zero1_dp":
        # pure DP: replicated bf16 params (no contraction resharding),
        # optimizer moments sharded over the whole mesh (ZeRO-1).
        rules = {k: None for k in meshlib.BASE_RULES}
        dp_axes = ("pod", "data", "model")
        opt_rules = {k: None for k in meshlib.BASE_RULES}
        opt_rules["embed"] = ("data", "model")
        opt_rules["mlp"] = None
    else:
        rules = meshlib.rules_for(cfg, fsdp=n_params > FSDP_THRESHOLD)
        dp_axes = ("pod", "data")
    pshard = meshlib.shardings_for_tree(pshapes, paxes, rules, mesh)
    batch_specs = speclib.input_specs(cfg, cell)
    bshard = _batch_shardings(batch_specs, mesh, cfg, dp_axes=dp_axes)

    if cell.kind == "train":
        opt_shapes = jax.eval_shape(adamw.init, pshapes)
        orules = opt_rules if opt_rules is not None else rules
        mom_shard = meshlib.shardings_for_tree(
            opt_shapes.mu, paxes, orules, mesh)
        opt_shard = adamw.AdamWState(
            step=NamedSharding(mesh, P()), mu=mom_shard,
            nu=meshlib.shardings_for_tree(opt_shapes.nu, paxes, orules,
                                          mesh))
        step = steplib.make_train_step(cfg, AdamWConfig())
        jitted = jax.jit(step, in_shardings=(pshard, opt_shard, bshard),
                         donate_argnums=(0, 1))
        args = (pshapes, opt_shapes, batch_specs)
    elif cell.kind == "prefill":
        step = steplib.make_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=(pshard, bshard))
        args = (pshapes, batch_specs)
    else:
        step = steplib.make_decode_step(cfg)
        jitted = jax.jit(step, in_shardings=(pshard, bshard),
                         donate_argnums=(1,))
        args = (pshapes, batch_specs)

    mesh_name = "multipod512" if multi_pod else "pod256"
    name = f"{arch}__{cell.name}__{mesh_name}" + (f"__{tag}" if tag else "")
    rec = {"arch": arch, "shape": cell.name, "mesh": mesh_name,
           "chips": chips, "n_params": n_params, "kind": cell.kind,
           "sharding": sharding, "tag": tag}
    with jax.set_mesh(mesh) if hasattr(jax, "set_mesh") else mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    if verbose:
        print(f"[{name}] lowered {t_lower:.1f}s compiled {t_compile:.1f}s")
        print(compiled.memory_analysis())
    analysis = rooflib.analyze_compiled(compiled, chips)
    mf = rooflib.model_flops(cfg, cell)
    analysis["model_flops_total"] = mf
    total_hlo_flops = analysis["per_device_flops"] * chips
    analysis["useful_flop_ratio"] = (mf / total_hlo_flops
                                     if total_hlo_flops else None)
    rec.update(analysis)
    rec["lower_s"] = t_lower
    rec["compile_s"] = t_compile
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=1))
    if verbose:
        terms = analysis["terms_s"]
        print(f"[{name}] compute={terms['compute_s']:.4f}s "
              f"memory={terms['memory_s']:.4f}s "
              f"collective={terms['collective_s']:.4f}s "
              f"dominant={analysis['dominant']}")
    return rec


def run_drim_ann_cell(multi_pod: bool, out_dir: pathlib.Path = ART_DIR,
                      fused_scan: bool = False, lut_dtype=None,
                      tag: str = ""):
    """The paper's own workload as a dry-run cell: the sharded search step
    lowered on the production mesh (data axis = shards; queries replicated,
    exactly the engine's layout).

    ``lut_dtype="uint8"`` lowers the quantized-LUT fast path (LC's
    affine-quantize epilogue + u8 DC with per-subspace scales) so the
    cost analysis prices the 4x smaller LUT traffic; with ``fused_scan``
    the u8 entries stream through the C-block scan, mirroring
    ``pq_scan_topk_q_pallas``'s dataflow at HLO level."""
    from repro.configs import drim_ann
    from repro.core.pq import PQCodebook
    from repro.core.sharded_search import _shard_tasks_fn
    from jax.sharding import PartitionSpec as P

    dcfg = drim_ann.config()
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    shard_axes = tuple(a for a in ("pod", "data", "model")
                       if a in mesh.axis_names)
    # all mesh axes act as one flat 'DPU' pool.  Slot provisioning: split
    # parts ~ n_points/split_max, x2 for duplication headroom (paper: ~10%
    # memory budget, we provision generously for the static shape).
    cpart = dcfg.split_max
    n_instances = 2 * max(dcfg.n_points // dcfg.split_max, dcfg.nlist)
    slots = max(-(-n_instances // chips), 1)
    tasks = dcfg.tasks_per_shard
    m, cb, d = dcfg.m, dcfg.cb, dcfg.dim
    dsub = d // m
    f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8

    def search_step(codes, ids, sizes, cluster_of, qidx, sidx, queries,
                    centroids, codebooks, sqnorms):
        cbk = PQCodebook(codebooks, sqnorms)
        # NOTE: the jnp path lowers the DC phase with the *gather*
        # dataflow — the HBM traffic the fused Pallas kernel actually
        # performs (codes stream + LUT lookups).  The onehot-MXU form is
        # an intra-kernel (VMEM-block) rewrite; expressed at HLO level it
        # would materialize a (T, C, M*CB) one-hot, which is neither what
        # the kernel does nor lowerable at 100M scale.
        bd, bi = _shard_tasks_fn(codes[0], ids[0], sizes[0], cluster_of[0],
                                 qidx[0], sidx[0], queries, centroids,
                                 cbk, None, k=dcfg.k, strategy="gather",
                                 use_kernels=False, fused_scan=fused_scan,
                                 lut_dtype=lut_dtype)
        return bd[None], bi[None]

    smap = jax.shard_map(
        search_step, mesh=mesh,
        in_specs=(P(shard_axes), P(shard_axes), P(shard_axes), P(shard_axes),
                  P(shard_axes), P(shard_axes), P(), P(), P(), P()),
        out_specs=(P(shard_axes), P(shard_axes)))
    jitted = jax.jit(smap)

    sds = jax.ShapeDtypeStruct
    args = (sds((chips, slots, cpart, m), u8),          # codes
            sds((chips, slots, cpart), i32),            # ids
            sds((chips, slots), i32),                   # sizes
            sds((chips, slots), i32),                   # cluster_of
            sds((chips, tasks), i32),                   # qidx
            sds((chips, tasks), i32),                   # sidx
            sds((dcfg.queries_per_batch, d), f32),      # queries
            sds((dcfg.nlist, d), f32),                  # centroids
            sds((m, cb, dsub), f32),                    # codebooks
            sds((m, cb), f32))                          # sqnorms
    t0 = time.time()
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    mesh_name = "multipod512" if multi_pod else "pod256"
    if not tag:
        tag = "__".join(p for p in (("fused" if fused_scan else ""),
                                    (f"lut_{lut_dtype}" if lut_dtype
                                     else "")) if p)
    name = f"drim_ann__search_100m__{mesh_name}" + (f"__{tag}" if tag else "")
    print(f"[{name}] lower+compile {time.time()-t0:.1f}s")
    print(compiled.memory_analysis())
    analysis = rooflib.analyze_compiled(compiled, chips)
    rec = {"arch": "drim_ann", "shape": "search_100m", "mesh": mesh_name,
           "chips": chips, "kind": "search", "tag": tag, **analysis}
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=1))
    terms = analysis["terms_s"]
    print(f"[{name}] compute={terms['compute_s']:.4f}s "
          f"memory={terms['memory_s']:.4f}s "
          f"collective={terms['collective_s']:.4f}s "
          f"dominant={analysis['dominant']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS + ("drim_ann",))
    ap.add_argument("--shape", choices=tuple(registry.SHAPES_BY_NAME))
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    # drim_ann cell variants (§Perf): fused C-block DC scan and/or the
    # quantized-LUT fast path (uint8 = PR 4's u8 ADC, lowered here so
    # cost_analysis prices the 4x smaller LUT traffic)
    ap.add_argument("--fused-scan", action="store_true",
                    help="drim_ann cell: stream DC over C-blocks with a "
                         "carried top-k (fused kernel dataflow)")
    ap.add_argument("--lut-dtype", choices=("f32", "bf16", "uint8"),
                    default=None,
                    help="drim_ann cell: LUT dtype (uint8 = full "
                         "quantized fast path, usable with or without "
                         "--fused-scan)")
    args = ap.parse_args()
    meshes = {"pod": (False,), "multipod": (True,),
              "both": (False, True)}[args.mesh]

    # CLI dtype names -> what _shard_tasks_fn expects ("uint8" stays a
    # string: it selects the quantize path, not a cast)
    lut_dtype = {None: None, "f32": None, "bf16": jnp.bfloat16,
                 "uint8": "uint8"}[args.lut_dtype]

    failures = []
    if args.all:
        todo = [(a, s, skip) for (a, s, skip) in registry.all_cells()]
        for mp in meshes:
            run_drim_ann_cell(mp, fused_scan=args.fused_scan,
                              lut_dtype=lut_dtype)
        for (a, s, skip) in todo:
            for mp in meshes:
                mesh_name = "multipod512" if mp else "pod256"
                if skip:
                    print(f"[{a}__{s.name}__{mesh_name}] {skip}")
                    continue
                fname = ART_DIR / f"{a}__{s.name}__{mesh_name}.json"
                if args.skip_existing and fname.exists():
                    continue
                try:
                    run_cell(a, s, mp)
                except Exception as e:
                    traceback.print_exc()
                    failures.append((a, s.name, mesh_name, repr(e)))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("ALL CELLS OK")
        return
    if args.arch == "drim_ann":
        for mp in meshes:
            run_drim_ann_cell(mp, fused_scan=args.fused_scan,
                              lut_dtype=lut_dtype)
        return
    cell = registry.SHAPES_BY_NAME[args.shape]
    for mp in meshes:
        run_cell(args.arch, cell, mp)


if __name__ == "__main__":
    main()
