import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
combination on the production meshes, with NO real allocation (all inputs
are ShapeDtypeStructs).

Per combination this produces:
  * proof the sharding config is coherent (compile succeeds),
  * ``compiled.memory_analysis()``  (fits-per-device evidence),
  * ``compiled.cost_analysis()``    (HLO FLOPs / bytes for the roofline),
  * collective bytes parsed from the optimized HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute),
written as JSON for `benchmarks.roofline`.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, InputShape
from repro.data.batches import batch_struct
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import batch_pspecs, cache_pspecs, param_pspecs
from repro.models.model import init_cache, init_model
from repro.optim.optimizers import adagrad_init
from repro.train.steps import (make_prefill_step, make_serve_step,
                               make_train_step)

PARAM_DTYPE = jnp.bfloat16

# Documented skips (DESIGN.md §5): long_500k needs sub-quadratic context.
LONG_OK = {"falcon-mamba-7b", "zamba2-1.2b", "mixtral-8x22b"}


def skip_reason(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" and cfg.arch_id not in LONG_OK:
        return ("full-attention family: 500k decode requires sub-quadratic "
                "attention (DESIGN.md §5)")
    return None


def input_specs(cfg: ModelConfig, shape: InputShape) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of this shape."""
    if shape.kind in ("train", "prefill"):
        structs = batch_struct(cfg, shape.global_batch, shape.seq_len)
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in
                structs.items()}
    # decode: one new token against a cache of seq_len context
    tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), np.int32)
    cache = jax.eval_shape(
        lambda: init_cache(cfg, shape.global_batch, shape.seq_len,
                           dtype=PARAM_DTYPE))
    return {"tokens": tokens, "cache": cache}


def params_specs(cfg: ModelConfig):
    return jax.eval_shape(
        lambda: init_model(cfg, jax.random.PRNGKey(0),
                           param_dtype=PARAM_DTYPE))


_COLL_RE = re.compile(
    r"(\w+\[[^\]]*\])[^=]*=\s*(all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)\(", re.I)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8,
                "f8e4m3fn": 1, "f8e5m2": 1, "s16": 2, "u16": 2}


def _tuple_shapes(text: str):
    """All 'dtype[dims]' occurrences inside one result-type string."""
    out = []
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n * _DTYPE_BYTES[dt])
    return out


_HDR_RE = re.compile(r"^(ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_WHILE_RE = re.compile(
    r"while\([^)]*\),\s*condition=(%[\w.\-]+),\s*body=(%[\w.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_COLL_LINE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[^\]]*\](?:\{[^}]*\})?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)\(")


def _split_computations(hlo_text: str) -> Dict[str, str]:
    """Map computation name -> its text block (optimized-HLO printing:
    headers at column 0, closing '}' at column 0)."""
    blocks: Dict[str, str] = {}
    cur_name, cur_lines = None, []
    for line in hlo_text.splitlines():
        m = _HDR_RE.match(line)
        if m:
            cur_name, cur_lines = m.group(2), []
            continue
        if line.startswith("}") and cur_name is not None:
            blocks[cur_name] = "\n".join(cur_lines)
            cur_name = None
            continue
        if cur_name is not None:
            cur_lines.append(line)
    return blocks


def collective_bytes(hlo_text: str, default_trip: float = 1.0
                     ) -> Dict[str, float]:
    """Per-device collective bytes from the optimized HLO, *execution-count
    aware*: XLA prints a while body once, so collectives inside scanned
    layer stacks are scaled by the loop's trip count (parsed from the
    comparison constant in the condition computation; falls back to
    ``default_trip`` = n_layers when unparseable).  Nested loops multiply.

    Accounting per device: all-reduce = 2x result bytes (ring);
    all-gather / reduce-scatter / all-to-all / collective-permute =
    1x result bytes (result shapes are post-SPMD per-device shapes).
    """
    blocks = _split_computations(hlo_text)
    # while-call graph: body -> (parent_block, trip_count)
    parent: Dict[str, str] = {}
    trip: Dict[str, float] = {}
    for name, text in blocks.items():
        for m in _WHILE_RE.finditer(text):
            cond, body = m.group(1), m.group(2)
            consts = [int(c) for c in _CONST_RE.findall(
                blocks.get(cond, ""))]
            trips = [c for c in consts if c > 1]
            trip[body] = float(max(trips)) if trips else default_trip
            parent[body] = name

    def multiplier(name: str, depth=0) -> float:
        if depth > 16 or name not in parent:
            return 1.0
        return trip.get(name, 1.0) * multiplier(parent[name], depth + 1)

    per_op: Dict[str, float] = {}
    for name, text in blocks.items():
        mult = multiplier(name) if name in parent else 1.0
        for m in _COLL_LINE_RE.finditer(text):
            result_ty, op = m.group(1), m.group(2)
            nbytes = sum(_tuple_shapes(result_ty))
            w = (2.0 if op == "all-reduce" else 1.0) * mult
            per_op[op] = per_op.get(op, 0.0) + w * nbytes
    return per_op


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               pm_miss_capacity: int = 0, zero_embed_head: bool = True,
               prefill_last_only: bool = False, vp_loss: bool = False,
               remat_policy: str = "full", pad_vocab: bool = False,
               zero_layers=True, fsdp_gather: bool = False,
               verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    if pad_vocab:
        import dataclasses
        pad_to = 16 * 128
        v = -(-cfg.vocab_size // pad_to) * pad_to
        cfg = dataclasses.replace(cfg, vocab_size=v)
    shape = SHAPES[shape_name]
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "pm_miss_capacity": pm_miss_capacity,
        "zero_embed_head": zero_embed_head,
        "prefill_last_only": prefill_last_only,
        "vp_loss": vp_loss,
        "remat_policy": remat_policy,
        "pad_vocab": pad_vocab,
        "zero_layers": "auto" if zero_layers is None else zero_layers,
        "fsdp_gather": fsdp_gather,
    }
    reason = skip_reason(cfg, shape)
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    p_sds = params_specs(cfg)
    p_spec = param_pspecs(p_sds, cfg, mesh, zero_embed_head=zero_embed_head,
                          zero_layers=zero_layers)
    from repro.launch.sharding import needs_zero
    zl_effective = needs_zero(cfg, mesh) if zero_layers is None \
        else zero_layers
    rec["zero_layers_effective"] = zl_effective
    fsdp_spec = None
    if fsdp_gather and zl_effective and "layers" in p_sds:
        layer_sds = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            p_sds["layers"])
        fsdp_spec = param_pspecs(layer_sds, cfg, mesh, zero_layers=False)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_sds = jax.eval_shape(adagrad_init, p_sds)
            opt_spec = type(opt_sds)(accum=param_pspecs(
                opt_sds.accum, cfg, mesh, zero_embed_head=zero_embed_head,
                zero_layers=zero_layers))
            b_sds = input_specs(cfg, shape)
            if pm_miss_capacity:
                C = 4096
                b_sds = dict(
                    b_sds,
                    pm_cache_ids=jax.ShapeDtypeStruct((C,), np.int32),
                    pm_cache_rows=jax.ShapeDtypeStruct(
                        (C, cfg.d_model), PARAM_DTYPE))
            b_spec = batch_pspecs(cfg, mesh, b_sds)
            from jax.sharding import PartitionSpec as P
            # the shard_map vocab-parallel CE needs V % model-axis == 0
            vp_ok = vp_loss and cfg.vocab_size % mesh.shape["model"] == 0
            fn = make_train_step(cfg, pm_miss_capacity=pm_miss_capacity,
                                 pm_strict=bool(pm_miss_capacity),
                                 remat_policy=remat_policy,
                                 vp_loss_mesh=mesh if vp_ok else None,
                                 fsdp_spec=fsdp_spec)
            jitted = jax.jit(
                fn,
                in_shardings=(jax.tree_util.tree_map(
                    lambda s: jax.NamedSharding(mesh, s), p_spec),
                    jax.tree_util.tree_map(
                        lambda s: jax.NamedSharding(mesh, s), opt_spec),
                    jax.tree_util.tree_map(
                        lambda s: jax.NamedSharding(mesh, s), b_spec)),
            )
            lowered = jitted.lower(p_sds, opt_sds, b_sds)
        elif shape.kind == "prefill":
            b_sds = input_specs(cfg, shape)
            b_spec = batch_pspecs(cfg, mesh, b_sds)
            fn = make_prefill_step(cfg, last_only=prefill_last_only,
                                   fsdp_spec=fsdp_spec)
            jitted = jax.jit(
                fn,
                in_shardings=(jax.tree_util.tree_map(
                    lambda s: jax.NamedSharding(mesh, s), p_spec),
                    jax.tree_util.tree_map(
                        lambda s: jax.NamedSharding(mesh, s), b_spec)),
            )
            lowered = jitted.lower(p_sds, b_sds)
        else:  # decode
            spec_in = input_specs(cfg, shape)
            cache_sds = spec_in["cache"]
            c_spec = cache_pspecs(cfg, mesh, cache_sds)
            tok_sds = spec_in["tokens"]
            from jax.sharding import PartitionSpec as P
            from repro.launch.mesh import batch_axes
            baxes = batch_axes(mesh)
            bsize = int(np.prod([mesh.shape[a] for a in baxes]))
            tok_spec = P(baxes if shape.global_batch % bsize == 0 else None,
                         None)
            fn = make_serve_step(cfg, fsdp_spec=fsdp_spec)
            jitted = jax.jit(
                fn,
                in_shardings=(jax.tree_util.tree_map(
                    lambda s: jax.NamedSharding(mesh, s), p_spec),
                    jax.tree_util.tree_map(
                        lambda s: jax.NamedSharding(mesh, s), c_spec),
                    jax.NamedSharding(mesh, tok_spec)),
            )
            lowered = jitted.lower(p_sds, cache_sds, tok_sds)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
            }
    except Exception:
        pass
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, default_trip=float(cfg.n_layers))

    rec.update({
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # raw cost_analysis values count while bodies ONCE (calibrated);
        # benchmarks.roofline combines them with analytic layer-scaled
        # estimates — see EXPERIMENTS.md §Dry-run methodology.
        "flops_raw": cost.get("flops", 0.0),
        "bytes_accessed_raw": cost.get("bytes accessed", 0.0),
        "collective_bytes_per_op": coll,
        "collective_bytes": sum(coll.values()),
        "memory": mem,
        "n_devices": int(np.prod(list(mesh.shape.values()))),
        "hlo_bytes": len(hlo),
    })
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: OK "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s, "
              f"raw GFLOPs {rec['flops_raw']/1e9:.1f}, "
              f"coll {rec['collective_bytes']/1e6:.1f}MB)")
        if mem:
            print(f"         memory_analysis: {mem}")
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--pm-miss-capacity", type=int, default=0)
    ap.add_argument("--no-zero-embed-head", dest="zero_embed_head",
                    action="store_false",
                    help="perf: keep embed/head vocab-sharded only "
                         "(kills the logits partial-sum all-reduce)")
    ap.add_argument("--prefill-last-only", action="store_true",
                    help="perf: head matmul on the final position only")
    ap.add_argument("--vp-loss", action="store_true",
                    help="perf: explicit vocab-parallel CE (shard_map)")
    ap.add_argument("--remat-policy", choices=("full", "dots"),
                    default="full",
                    help="perf: 'dots' saves matmul outputs (less "
                         "recompute, more activation memory)")
    ap.add_argument("--auto-zero-layers", action="store_true",
                    help="perf: ZeRO layer weights only when TP-only "
                         "weights+optimizer would not fit per-device")
    ap.add_argument("--fsdp-gather", action="store_true",
                    help="perf: constrain layer weights to their TP "
                         "layout inside the scan (gather weights, not "
                         "activations) when ZeRO is active")
    ap.add_argument("--pad-vocab", action="store_true",
                    help="perf: pad vocab to a multiple of 16*128 so the "
                         "embedding/head shard over the model axis")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    combos = []
    archs = ARCH_IDS if (args.all or not args.arch) else (args.arch,)
    shapes = tuple(SHAPES) if (args.all or not args.shape) else (args.shape,)
    meshes = (False, True) if args.both_meshes else (args.multi_pod,)
    for a in archs:
        for s in shapes:
            for mp in meshes:
                combos.append((a, s, mp))

    results = []
    for (a, s, mp) in combos:
        try:
            rec = dryrun_one(a, s, multi_pod=mp,
                             pm_miss_capacity=args.pm_miss_capacity,
                             zero_embed_head=args.zero_embed_head,
                             prefill_last_only=args.prefill_last_only,
                             vp_loss=args.vp_loss,
                             remat_policy=args.remat_policy,
                             pad_vocab=args.pad_vocab,
                             zero_layers=(None if args.auto_zero_layers
                                          else True),
                             fsdp_gather=args.fsdp_gather)
        except Exception as e:
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16",
                   "status": "error", "error": repr(e),
                   "trace": traceback.format_exc()[-2000:]}
            print(f"[dryrun] {a} x {s}: FAILED {e!r}", file=sys.stderr)
        results.append(rec)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    ok = sum(1 for r in results if r["status"] == "ok")
    sk = sum(1 for r in results if r["status"] == "skipped")
    err = sum(1 for r in results if r["status"] == "error")
    print(f"[dryrun] done: {ok} ok, {sk} skipped (documented), {err} failed")
    return 1 if err else 0


if __name__ == "__main__":
    sys.exit(main())
