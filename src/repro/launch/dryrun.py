import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes and record what each compiled program costs.

For each case this:
  1. builds the (16,16) single-pod or (2,16,16) multi-pod mesh,
  2. constructs parameter/optimizer/batch/cache ShapeDtypeStructs (zero
     allocation — weights never materialize),
  3. jits the train/prefill/decode step with explicit in/out shardings,
  4. ``.lower(...).compile()`` — success proves the distribution config is
     coherent (sharding divisibility, collective legality, layout),
  5. records ``memory_analysis()``, ``cost_analysis()`` and the collective
     traffic parsed from the post-SPMD optimized HLO into a JSON blob under
     ``experiments/dryrun/``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3_8b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--quant SINT]
"""

import argparse
import json
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_IDS, INPUT_SHAPES, ArchConfig, get_config
from repro.launch import shardings as sh
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import make_decode_step, make_optimizer, make_prefill, make_train_step
from repro.models.api import get_model

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# bytes-on-wire multiplier per collective (ring algorithms; documented
# approximation — see EXPERIMENTS.md §Dry-run)
_WIRE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(pred|[sbuf]\d+|bf16)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> Dict[str, Any]:
    """Sum output bytes of every collective op in optimized (post-SPMD) HLO."""
    per_op: Dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    counts: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        lhs, rhs = ls.split("=", 1)
        rhs = rhs.strip()
        matched = None
        for c in _COLLECTIVES:
            # opcode appears right after the output shape(s)
            if re.search(rf"\b{c}(-start|-done)?\(", rhs):
                matched = c
                break
        if matched is None:
            continue
        if f"{matched}-done(" in rhs:
            continue  # counted at -start
        # output shape(s): everything before the opcode token
        head = rhs.split(matched)[0]
        shapes = _SHAPE_RE.findall(head)
        nbytes = sum(_shape_bytes(d, dims) for d, dims in shapes)
        per_op[matched] += nbytes
        counts[matched] += 1
    wire = sum(per_op[c] * _WIRE_FACTOR[c] for c in _COLLECTIVES)
    return {"bytes_by_type": per_op, "counts": counts, "wire_bytes": wire}


def _spec_tree_bytes(tree: Any) -> int:
    return sum(int(np.prod(l.shape)) * l.dtype.itemsize
               for l in jax.tree.leaves(tree))


def effective_config(arch: str, shape: str, quant: Optional[str] = None,
                     unroll: bool = False,
                     n_layers: Optional[int] = None,
                     overrides: Optional[dict] = None) -> ArchConfig:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.with_(**overrides)
    if n_layers is not None:
        cfg = cfg.with_(n_layers=n_layers)
    if unroll:
        # Full unroll of the layer scan: XLA's cost analysis counts a while
        # body once, so honest FLOP/byte/collective totals need the layers in
        # the HLO.  Compile cost is higher.
        n_stacked = cfg.n_layers // (cfg.attn_period or 1) if cfg.family == "hybrid" else cfg.n_layers
        cfg = cfg.with_(scan_unroll=max(n_stacked, 1))
    shp = INPUT_SHAPES[shape]
    if shape == "long_500k" and shp["kind"] == "decode":
        # sub-quadratic requirement: full-attention archs get the SWA variant
        if cfg.family in ("dense", "moe", "vlm", "audio") and cfg.sliding_window is None:
            cfg = cfg.with_(sliding_window=cfg.swa_for_long,
                            notes=cfg.notes + " [long_500k: SWA substituted]")
    if quant:
        cfg = cfg.with_(quant=quant)
    return cfg


def build_case(arch: str, shape: str, mesh, quant: Optional[str] = None,
               unroll: bool = False, n_layers: Optional[int] = None,
               overrides: Optional[dict] = None):
    """Returns (jitted_fn, arg_specs, meta) ready to lower."""
    cfg = effective_config(arch, shape, quant, unroll, n_layers, overrides)
    api = get_model(cfg)
    shp = INPUT_SHAPES[shape]
    batch, seq = shp["global_batch"], shp["seq_len"]
    kind = shp["kind"]

    sh.install_hook(mesh, batch_sharded=(kind != "decode" or batch > 1),
                    seq_parallel=cfg.seq_parallel)
    p_specs = api.param_specs()
    p_shard = sh.param_shardings(p_specs, cfg, mesh)
    b_specs = api.batch_specs(kind, batch, seq)
    b_shard = sh.batch_shardings(b_specs, mesh, batch_size=batch)

    meta = {
        "arch": arch, "shape": shape, "kind": kind,
        "global_batch": batch, "seq_len": seq,
        "param_bytes": _spec_tree_bytes(p_specs),
        "quant": quant,
    }

    if kind == "train":
        opt_init, opt_update = make_optimizer()
        o_specs = jax.eval_shape(opt_init, p_specs)
        o_shard = sh.opt_shardings(o_specs, p_shard, mesh)
        step = make_train_step(api, opt_update)
        fn = jax.jit(
            step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1),
        )
        args = (p_specs, o_specs, b_specs)
        meta["opt_bytes"] = _spec_tree_bytes(o_specs)
    elif kind == "prefill":
        step = make_prefill(api, cache_len=seq)
        c_specs = api.cache_specs(batch, seq)
        c_shard = sh.cache_shardings(c_specs, cfg, mesh, batch_size=batch)
        fn = jax.jit(step, in_shardings=(p_shard, b_shard),
                     out_shardings=(c_shard, None))
        args = (p_specs, b_specs)
        meta["cache_bytes"] = _spec_tree_bytes(c_specs)
    else:  # decode
        step = make_decode_step(api)
        c_specs = api.cache_specs(batch, seq)
        c_shard = sh.cache_shardings(c_specs, cfg, mesh, batch_size=batch)
        pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
        fn = jax.jit(
            step,
            in_shardings=(p_shard, c_shard, b_shard, sh.NamedSharding(mesh, sh.P())),
            out_shardings=(c_shard, None),
            donate_argnums=(1,),
        )
        args = (p_specs, c_specs, b_specs, pos_spec)
        meta["cache_bytes"] = _spec_tree_bytes(c_specs)

    return fn, args, meta


def _compile_case(arch: str, shape: str, mesh, quant, unroll, n_layers=None,
                  overrides=None):
    t0 = time.time()
    fn, args, meta = build_case(arch, shape, mesh, quant, unroll, n_layers,
                                overrides)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    return compiled, meta, t_lower, t_compile


# models small enough to compile fully unrolled; everything bigger uses the
# L=1 / L=2 extrapolation (total = outer + L*body, body = c2 - c1).
_FULL_UNROLL_BYTES = 10e9


def _case_costs(compiled) -> Dict[str, float]:
    cost = compiled.cost_analysis() or {}
    coll = parse_collectives(compiled.as_text())
    out = {"flops": float(cost.get("flops", 0.0)),
           "bytes": float(cost.get("bytes accessed", 0.0)),
           "wire_bytes": float(coll["wire_bytes"])}
    for c in _COLLECTIVES:
        out[f"coll_{c}"] = float(coll["bytes_by_type"][c] * _WIRE_FACTOR[c])
    return out


def run_case(arch: str, shape: str, *, multi_pod: bool = False,
             quant: Optional[str] = None, save: bool = True,
             unroll: bool = False, costs: bool = False,
             overrides: Optional[dict] = None,
             tag: Optional[str] = None) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    fn, args, meta = build_case(arch, shape, mesh, quant, unroll,
                                overrides=overrides)
    meta["variant"] = tag
    meta["unrolled"] = unroll
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # some backends lack memory_analysis
        mem_d = {"error": str(e)}

    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    result = {
        **meta,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips,
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float)) and not k.startswith("utilization")},
        "memory_analysis": mem_d,
        "collectives": coll,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
    }

    if costs:
        # Honest per-device totals: XLA counts a while(scan) body once, so we
        # recover total = outer + L*body from two small unrolled compiles
        # (L=1, L=2) at full width on the same mesh, or one fully unrolled
        # compile when the model is small enough.
        cfg0 = effective_config(arch, shape, quant, overrides=overrides)
        period = cfg0.attn_period if cfg0.family == "hybrid" else 1
        n_stack = cfg0.n_layers // max(period, 1)
        if meta["param_bytes"] < _FULL_UNROLL_BYTES or n_stack <= 2:
            cu, _, _, tcu = _compile_case(arch, shape, mesh, quant, True,
                                          overrides=overrides)
            result["cost_totals"] = {**_case_costs(cu), "method": "full_unroll",
                                     "compile_s": round(tcu, 2)}
        else:
            c1, _, _, t1 = _compile_case(arch, shape, mesh, quant, True,
                                         n_layers=1 * period, overrides=overrides)
            c2, _, _, t2 = _compile_case(arch, shape, mesh, quant, True,
                                         n_layers=2 * period, overrides=overrides)
            a, b = _case_costs(c1), _case_costs(c2)
            tot = {}
            for k in a:
                body = b[k] - a[k]
                tot[k] = a[k] + (n_stack - 1) * max(body, 0.0)
            result["cost_totals"] = {**tot, "method": "extrapolate_1_2",
                                     "compile_s": round(t1 + t2, 2)}
    sh.install_hook(None)

    if save:
        os.makedirs(OUT_DIR, exist_ok=True)
        fname = f"{arch}__{shape}__{'2x16x16' if multi_pod else '16x16'}"
        if quant:
            fname += f"__{quant}"
        if unroll:
            fname += "__unrolled"
        if tag:
            fname += f"__{tag}"
        with open(os.path.join(OUT_DIR, fname + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--quant", choices=("SINT", "INT", "DINT"))
    ap.add_argument("--unroll", action="store_true",
                    help="fully unroll the layer scan (accurate cost totals)")
    ap.add_argument("--costs", action="store_true",
                    help="also derive honest cost totals (extra compiles)")
    args = ap.parse_args()

    cases = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cases.append((a, s, m))

    failures = 0
    for a, s, m in cases:
        tag = f"{a:24s} {s:12s} {'2x16x16' if m else '16x16 '}"
        try:
            r = run_case(a, s, multi_pod=m, quant=args.quant,
                         unroll=args.unroll, costs=args.costs)
            print(f"OK   {tag} flops={r['hlo_flops']:.3e} "
                  f"bytes={r['hlo_bytes']:.3e} "
                  f"coll={r['collectives']['wire_bytes']:.3e} "
                  f"compile={r['compile_s']}s", flush=True)
        except Exception as e:
            failures += 1
            print(f"FAIL {tag} {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures}/{len(cases)} dry-run cases failed")
    print(f"all {len(cases)} dry-run cases compiled")


if __name__ == "__main__":
    main()
