import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import (jax locks the
# device count on first init). Everything else follows.

r"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape x mesh) combination, build the
production mesh from 512 host-platform placeholder devices, lower the
appropriate step function with ShapeDtypeStruct inputs (no allocation),
compile it, and record memory_analysis / cost_analysis / per-collective
byte counts for the roofline (benchmarks/roofline.py reads the JSON).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k \
      --mesh single --out experiments/dryrun
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 2]
"""
import argparse
import json
import re
import subprocess
import sys
import time
from functools import partial
from typing import Any, Dict

import jax

from repro.configs import get_config
from repro.configs.registry import ARCH_IDS
from repro.configs.shapes import SHAPES, InputShape, batch_specs, cache_window
from repro.models import model as lm
from repro.models.common import ModelConfig
from repro.parallel.sharding import (batch_specs_sharding,
                                     decode_state_specs, logical_rules,
                                     param_shardings, rules_for)
from repro.serve import engine
from repro.train.optim import OptimConfig, init_opt_state
from repro.train.train_step import train_step
from .mesh import make_production_mesh

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _bytes_of_type_str(s: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(s):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Sum result bytes of every collective op in the HLO text, keyed by
    op kind, plus op counts."""
    out = {op: {"bytes": 0, "count": 0} for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        lhs, _, rhs = line.partition("=")
        rhs = rhs.strip()
        for op in COLLECTIVE_OPS:
            # match op name at the call position: "<type> opname("
            mm = re.match(r"(.+?)\s(%?" + op + r")[.\d]*\(", rhs)
            if mm and not rhs.startswith("fusion"):
                out[op]["bytes"] += _bytes_of_type_str(mm.group(1))
                out[op]["count"] += 1
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    out["total_count"] = sum(v["count"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _eval_shapes(fn, *args):
    return jax.eval_shape(fn, *args)


def build_dryrun(cfg: ModelConfig, shape: InputShape, multi_pod: bool):
    """Returns (jitted_fn, arg_specs, in_shardings) ready to lower."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    long_ctx = shape.name == "long_500k"
    rules = rules_for(mesh, shard_cache_seq=long_ctx)
    if long_ctx:
        rules["batch"] = None      # global_batch=1: nothing to shard

    param_shapes = _eval_shapes(
        lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
    p_shardings = param_shardings(param_shapes, mesh, rules,
                                  n_expert_hint=cfg.n_experts)
    b_specs = batch_specs(cfg, shape)
    b_shardings = batch_specs_sharding(b_specs, mesh, rules)

    if shape.kind == "train":
        opt_cfg = OptimConfig()
        opt_shapes = _eval_shapes(partial(init_opt_state), param_shapes)
        o_shardings = {
            "mu": p_shardings, "nu": jax.tree_util.tree_map(
                lambda s: s, p_shardings),
            "step": jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())}

        def fn(params, opt_state, batch):
            with logical_rules(rules):
                new_p, new_o, metrics = train_step(cfg, opt_cfg, params,
                                                   opt_state, batch)
            return new_p, new_o, metrics["loss"]

        jitted = jax.jit(fn, in_shardings=(p_shardings, o_shardings,
                                           b_shardings),
                         out_shardings=(p_shardings, o_shardings, None))
        args = (param_shapes, opt_shapes, b_specs)
    elif shape.kind == "prefill":
        def fn(params, batch):
            with logical_rules(rules):
                logits, _ = lm.forward(cfg, params, batch)
            return logits

        jitted = jax.jit(fn, in_shardings=(p_shardings, b_shardings))
        args = (param_shapes, b_specs)
    else:  # decode
        window = cache_window(cfg, shape)
        state_shapes = _eval_shapes(
            lambda: engine.init_state(cfg, shape.global_batch, window))
        s_shardings = decode_state_specs(state_shapes, mesh, rules)

        def fn(params, state, batch):
            with logical_rules(rules):
                logits, new_state = engine.serve_step(cfg, params, state,
                                                      batch)
            return logits, new_state

        jitted = jax.jit(fn, in_shardings=(p_shardings, s_shardings,
                                           b_shardings),
                         out_shardings=(None, s_shardings))
        args = (param_shapes, state_shapes, b_specs)
    return mesh, jitted, args


def probe_depths(cfg: ModelConfig):
    """Two reduced depths for the unrolled cost probes (XLA counts scan
    bodies once, so true per-layer cost comes from the probe slope)."""
    if cfg.arch_type == "ssm":
        return (cfg.slstm_every, 2 * cfg.slstm_every)
    if cfg.arch_type == "hybrid":
        return (cfg.shared_attn_every, 2 * cfg.shared_attn_every)
    if cfg.arch_type == "moe":
        k = cfg.first_k_dense
        return (k + 1, k + 3)
    return (2, 4)


def _cost_dict(compiled):
    """``Compiled.cost_analysis()`` as a dict (it returns ``None`` when
    the backend has no cost model)."""
    return compiled.cost_analysis() or {}


def _compile_cost(cfg: ModelConfig, shape: InputShape, multi_pod: bool):
    mesh, jitted, args = build_dryrun(cfg, shape, multi_pod)
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
        cost = _cost_dict(compiled)
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
    coll = parse_collective_bytes(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
        "bytes": float(cost.get("bytes accessed", 0.0)) if cost else 0.0,
        "collective_bytes": coll["total_bytes"],
        "collectives": coll,
    }


def run_one(arch: str, shape_name: str, mesh_kind: str,
            probe: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    multi_pod = mesh_kind == "multi"
    t0 = time.time()
    mesh, jitted, args = build_dryrun(cfg, shape, multi_pod)
    with mesh:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = _cost_dict(compiled)
        try:
            hlo = compiled.as_text()
        except Exception:
            hlo = lowered.as_text()
    coll = parse_collective_bytes(hlo)
    chips = 512 if multi_pod else 256

    probes = None
    if probe:
        l_small, l_big = probe_depths(cfg)
        c_small = _compile_cost(
            cfg.replace(n_layers=l_small, force_unscanned=True),
            shape, multi_pod)
        c_big = _compile_cost(
            cfg.replace(n_layers=l_big, force_unscanned=True),
            shape, multi_pod)
        span = l_big - l_small
        L = cfg.n_layers

        def extrap(key):
            slope = (c_big[key] - c_small[key]) / span
            return c_small[key] + slope * (L - l_small), slope

        flops_t, flops_slope = extrap("flops")
        bytes_t, bytes_slope = extrap("bytes")
        coll_t, coll_slope = extrap("collective_bytes")
        probes = {
            "depths": [l_small, l_big],
            "small": c_small, "big": c_big,
            "per_layer": {"flops": flops_slope, "bytes": bytes_slope,
                          "collective_bytes": coll_slope},
            "extrapolated": {"flops": flops_t, "bytes": bytes_t,
                             "collective_bytes": coll_t},
        }

    def _mem_field(name):
        try:
            v = getattr(mem, name)
            return int(v) if v is not None else None
        except Exception:
            return None

    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": chips,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": float(cost.get("flops", -1)) if cost else None,
        "bytes_accessed": float(cost.get("bytes accessed", -1))
        if cost else None,
        "memory_analysis": {
            k: _mem_field(k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")},
        "collectives": coll,
        "hlo_ops": len(hlo.splitlines()),
        "probes": probes,
    }
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="")
    ap.add_argument("--shape", type=str, default="",
                    choices=[""] + list(SHAPES))
    ap.add_argument("--mesh", type=str, default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", type=str, default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip the unrolled cost probes (multi-pod gate)")
    ap.add_argument("--archs", type=str, default="",
                    help="comma-separated arch filter for --all")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    if not args.all:
        assert args.arch and args.shape and args.mesh != "both"
        res = run_one(args.arch, args.shape, args.mesh,
                      probe=not args.no_probe)
        path = os.path.join(
            args.out, f"{args.arch}__{args.shape}__{args.mesh}.json")
        with open(path, "w") as f:
            json.dump(res, f, indent=1)
        print(json.dumps({k: res[k] for k in
                          ("arch", "shape", "mesh", "compile_s", "flops",
                           "bytes_accessed")}))
        print("collectives:", json.dumps(res["collectives"]))
        return 0

    # --all: one subprocess per combo (isolates XLA state and failures)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = [a for a in ARCH_IDS
             if not args.archs or a in args.archs.split(",")]
    failures = []
    for arch in archs:
        for shape_name in SHAPES:
            for mk in meshes:
                path = os.path.join(
                    args.out, f"{arch}__{shape_name}__{mk}.json")
                if os.path.exists(path) and not args.force:
                    print(f"skip {path}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape_name,
                       "--mesh", mk, "--out", args.out]
                if mk == "multi" or args.no_probe:
                    cmd.append("--no-probe")  # roofline is single-pod
                print(">>", arch, shape_name, mk, flush=True)
                t0 = time.time()
                r = subprocess.run(cmd, capture_output=True, text=True)
                dt = time.time() - t0
                if r.returncode != 0:
                    failures.append((arch, shape_name, mk))
                    print(f"FAIL ({dt:.0f}s)\n{r.stdout[-2000:]}"
                          f"\n{r.stderr[-4000:]}", flush=True)
                else:
                    print(f"ok ({dt:.0f}s) {r.stdout.strip()[:300]}",
                          flush=True)
    if failures:
        print("FAILURES:", failures)
        return 1
    print("all dry-runs passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
