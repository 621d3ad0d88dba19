import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, WITHOUT allocating a single model byte (ShapeDtypeStruct stand-ins).

    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape decode_32k --multipod
    PYTHONPATH=src python -m repro.launch.dryrun --sweep          # all cells, subprocesses

Per cell this prints/records compiled.memory_analysis() (fits-in-HBM proof)
and cost_analysis() + parsed collective bytes (the §Roofline terms), cached
as JSON under results/dryrun/.
"""

import argparse
import json
import subprocess
import sys
import time

# NOTE: jax is imported only after XLA_FLAGS is set (line 2).
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, ARCH_IDS, applicable_shapes, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import cache_specs, input_specs, param_specs
from repro.models import forward
from repro.optim.optimizers import make_optimizer
from repro.roofline.analysis import analyze, collective_bytes
from repro.sharding import Policy
from repro.train.step import build_train_step

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def make_policy(mesh, cfg) -> Policy:
    multi = "pod" in mesh.axis_names
    return Policy(mesh=mesh, pod_axis="pod" if multi else None,
                  fsdp=True, fsdp_over_pod=multi, seq_shard=True)


def batch_shardings(policy, batch_spec):
    out = {}
    for k, v in batch_spec.items():
        if k == "cache_len" or v.ndim == 0:
            out[k] = NamedSharding(policy.mesh, P())
        else:
            b = policy.phys("batch")
            if not _div(v.shape[0], policy, b):
                b = None          # e.g. long_500k global_batch=1: replicate
            out[k] = NamedSharding(policy.mesh,
                                   P(b, *([None] * (v.ndim - 1))))
    return out


def cache_shardings(policy, cspec):
    def leaf_spec(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        batch = policy.phys("batch")
        if name in ("k", "v"):
            # (n_super, B, KH, S, hd): batch over data; model axis carries
            # head_dim ("kvdim") or sequence ("kvseq") per policy.kv_layout.
            b = batch if _div(leaf.shape[1], policy, batch) else None
            if policy.kv_layout == "kvseq":
                sq = (policy.model_axis
                      if leaf.shape[3] % policy.model_size == 0 else None)
                return NamedSharding(policy.mesh, P(None, b, None, sq, None))
            hd = leaf.shape[-1]
            kvdim = policy.phys("kvdim") if hd % policy.model_size == 0 else None
            return NamedSharding(policy.mesh, P(None, b, None, None, kvdim))
        if name == "ssm":
            b = batch if _div(leaf.shape[1], policy, batch) else None
            h = (policy.model_axis
                 if leaf.shape[2] % policy.model_size == 0 else None)
            return NamedSharding(policy.mesh, P(None, b, h, None, None))
        if name == "conv":
            b = batch if _div(leaf.shape[1], policy, batch) else None
            c = (policy.model_axis
                 if leaf.shape[-1] % policy.model_size == 0 else None)
            return NamedSharding(policy.mesh, P(None, b, None, c))
        return NamedSharding(policy.mesh, P())
    flat, treedef = jax.tree_util.tree_flatten_with_path(cspec)
    return jax.tree_util.tree_unflatten(
        treedef, [leaf_spec(p, l) for p, l in flat])


def _div(dim, policy, axes):
    if axes is None:
        return False
    sizes = [policy.axis_size(a) for a in (axes if isinstance(axes, tuple) else (axes,))]
    n = 1
    for s in sizes:
        n *= s
    return dim % n == 0


def opt_state_specs(cfg, optimizer, pspecs):
    return jax.eval_shape(optimizer.init, pspecs)


def _lower_shallow(cfg, cell, shape_name, policy, mesh, n_super: int):
    """Lower an unrolled shallow variant (n_super superblocks) and return
    (flops, bytes, coll_bytes) per device."""
    import dataclasses
    # attn_chunk bump: identical flops (masking pattern unchanged), but the
    # unrolled KV scan stays at <= 4 steps for fast shallow compiles.
    scfg = dataclasses.replace(
        cfg, num_layers=n_super * cfg.block_period, grad_accum=1,
        unroll_scans=True,
        attn_chunk=max(cfg.attn_chunk, cell.seq_len // 4))
    pspecs = param_specs(scfg)
    pshard = policy.param_shardings(pspecs)
    bspec = input_specs(scfg, shape_name)
    bshard = batch_shardings(policy, bspec)
    if cell.kind == "train":
        optimizer = make_optimizer(scfg)
        state_spec = {"params": pspecs,
                      "opt": opt_state_specs(scfg, optimizer, pspecs),
                      "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_shard = {"params": pshard,
                       "opt": policy.param_shardings(state_spec["opt"]),
                       "step": NamedSharding(policy.mesh, P())}
        step_fn = build_train_step(scfg, policy, optimizer)
        compiled = jax.jit(step_fn, in_shardings=(state_shard, bshard),
                           donate_argnums=(0,)).lower(state_spec, bspec).compile()
    elif cell.kind == "prefill":
        def prefill_step(params, batch):
            logits, cache, _ = forward(params, batch, scfg, policy,
                                       mode="prefill")
            return logits[:, -1], cache
        compiled = jax.jit(prefill_step, in_shardings=(pshard, bshard)
                           ).lower(pspecs, bspec).compile()
    else:
        cspec = cache_specs(scfg, shape_name)
        cshard = cache_shardings(policy, cspec)

        def serve_step(params, cache, batch):
            logits, new_cache, _ = forward(params, batch, scfg, policy,
                                           mode="decode", cache=cache)
            return logits[:, -1], new_cache
        compiled = jax.jit(serve_step, in_shardings=(pshard, cshard, bshard),
                           donate_argnums=(1,)).lower(pspecs, cspec, bspec
                                                      ).compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            coll)


def _extrapolated_roofline(cfg, cell, shape_name, policy, mesh, chips):
    from repro.roofline.analysis import Roofline, model_flops, ssd_flops_fwd
    n_super = cfg.num_layers // cfg.block_period
    f1, b1, c1 = _lower_shallow(cfg, cell, shape_name, policy, mesh, 1)
    f2, b2, c2 = _lower_shallow(cfg, cell, shape_name, policy, mesh, 2)
    n = n_super - 1
    # clamp the per-superblock delta at 0: XLA sometimes optimizes the
    # depth-2 variant below depth-1 on cheap (decode) cells, and a small
    # negative delta would be amplified n_super-fold into nonsense.
    flops = f1 + n * max(f2 - f1, 0.0)
    byts = b1 + n * max(b2 - b1, 0.0)
    # SSD chunk scans always stay rolled (compile-time cap): add the
    # analytic flops the once-counted body misses.  Training ~= 4x forward
    # (fwd + full-remat recompute + bwd); decode has no chunk scan.
    if cfg.ssm_state and cell.kind in ("train", "prefill"):
        factor = 4.0 if cell.kind == "train" else 1.0
        flops += factor * ssd_flops_fwd(cfg, cell.global_batch,
                                        cell.seq_len) / chips
    coll_total = c1["total_bytes"] + n * max(
        c2["total_bytes"] - c1["total_bytes"], 0)
    coll = {
        "bytes": {k: c1["bytes"].get(k, 0)
                  + n * max(c2["bytes"].get(k, 0) - c1["bytes"].get(k, 0), 0)
                  for k in set(c1["bytes"]) | set(c2["bytes"])},
        "counts": {k: c1["counts"].get(k, 0)
                   + n * max(c2["counts"].get(k, 0) - c1["counts"].get(k, 0), 0)
                   for k in set(c1["counts"]) | set(c2["counts"])},
        "total_bytes": coll_total,
        "method": "depth-extrapolated (unrolled shallow lowers)",
    }
    roof = Roofline(flops=flops, bytes_accessed=byts,
                    coll_bytes=float(coll_total),
                    model_flops=model_flops(cfg, shape_name), chips=chips)
    return roof, {"collectives": coll}


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               policy_overrides: dict | None = None, verbose: bool = True,
               extrapolate: bool = True, keep_hlo: bool = False):
    """Lower + compile one (arch x shape x mesh) cell; return result dict."""
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    policy = make_policy(mesh, cfg)
    if policy_overrides:
        import dataclasses
        policy = dataclasses.replace(policy, **policy_overrides)

    pspecs = param_specs(cfg)
    pshard = policy.param_shardings(pspecs)
    bspec = input_specs(cfg, shape_name)
    bshard = batch_shardings(policy, bspec)

    t0 = time.time()
    if cell.kind == "train":
        optimizer = make_optimizer(cfg)
        state_spec = {"params": pspecs,
                      "opt": opt_state_specs(cfg, optimizer, pspecs),
                      "step": jax.ShapeDtypeStruct((), jnp.int32)}
        state_shard = {"params": pshard,
                       "opt": policy.param_shardings(state_spec["opt"]),
                       "step": NamedSharding(mesh, P())}
        step_fn = build_train_step(cfg, policy, optimizer)
        jf = jax.jit(step_fn, in_shardings=(state_shard, bshard),
                     donate_argnums=(0,))
        lowered = jf.lower(state_spec, bspec)
    elif cell.kind == "prefill":
        def prefill_step(params, batch):
            logits, cache, _ = forward(params, batch, cfg, policy,
                                       mode="prefill")
            return logits[:, -1], cache
        jf = jax.jit(prefill_step, in_shardings=(pshard, bshard))
        lowered = jf.lower(pspecs, bspec)
    else:  # decode
        cspec = cache_specs(cfg, shape_name)
        cshard = cache_shardings(policy, cspec)

        def serve_step(params, cache, batch):
            logits, new_cache, _ = forward(params, batch, cfg, policy,
                                           mode="decode", cache=cache)
            return logits[:, -1], new_cache
        jf = jax.jit(serve_step, in_shardings=(pshard, cshard, bshard),
                     donate_argnums=(1,))
        lowered = jf.lower(pspecs, cspec, bspec)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    if extrapolate:
        # XLA cost_analysis counts each scan body ONCE, so the full-depth
        # compile under-reports flops/bytes/collectives by the trip counts.
        # Exact accounting: lower depth-1 and depth-2 (superblock) variants
        # with inner scans unrolled; the per-superblock delta extrapolates
        # linearly (the stack is layer-homogeneous by construction).
        roof, extra = _extrapolated_roofline(cfg, cell, shape_name, policy,
                                             mesh, chips)
        coll = extra["collectives"]
    else:
        # multi-pod pass: compile + memory proof only (roofline table is
        # single-pod); raw body-once counts recorded for reference.
        roof = analyze(compiled, cfg, shape_name, chips)
        coll = collective_bytes(compiled.as_text())
        coll["method"] = "raw (scan bodies counted once)"
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "params_B": cfg.param_count() / 1e9,
        "active_params_B": cfg.active_param_count() / 1e9,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_GiB": mem.argument_size_in_bytes / 2**30,
            "output_GiB": mem.output_size_in_bytes / 2**30,
            "temp_GiB": mem.temp_size_in_bytes / 2**30,
            "alias_GiB": mem.alias_size_in_bytes / 2**30,
            "peak_per_device_GiB": (mem.argument_size_in_bytes
                                    + mem.output_size_in_bytes
                                    + mem.temp_size_in_bytes
                                    - mem.alias_size_in_bytes) / 2**30,
        },
        "collectives": coll,
        "roofline": roof.as_dict(),
    }
    if keep_hlo:
        result["_hlo"] = compiled.as_text()
    if verbose:
        print(json.dumps({k: v for k, v in result.items() if k != "_hlo"},
                         indent=2))
    return result


def cell_path(arch, shape_name, multi_pod):
    mesh = "2x16x16" if multi_pod else "16x16"
    d = os.path.join(RESULTS_DIR, mesh)
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch}__{shape_name}.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--sweep", action="store_true",
                    help="run every applicable cell in subprocesses")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    if args.sweep:
        failures = []
        meshes = [False, True] if args.both_meshes else [args.multipod]
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            for shape in applicable_shapes(cfg):
                for mp in meshes:
                    out = cell_path(arch, shape, mp)
                    if os.path.exists(out) and not args.force:
                        print(f"skip (cached): {out}")
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape]
                    if mp:
                        cmd.append("--multipod")
                    print(">>", " ".join(cmd), flush=True)
                    r = subprocess.run(cmd)
                    if r.returncode != 0:
                        failures.append((arch, shape, mp))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("sweep complete")
        return

    assert args.arch and args.shape, "--arch and --shape required"
    cfg = get_config(args.arch)
    if args.shape not in applicable_shapes(cfg):
        print(f"SKIP: {args.arch} x {args.shape} not applicable "
              f"(long_500k is sub-quadratic-only; see DESIGN.md)")
        return
    result = lower_cell(args.arch, args.shape, multi_pod=args.multipod,
                        extrapolate=not args.multipod)
    with open(cell_path(args.arch, args.shape, args.multipod), "w") as f:
        json.dump(result, f, indent=2)


if __name__ == "__main__":
    main()
