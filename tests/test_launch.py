"""Launch layer: mesh construction, sharding specs, collective-byte
parser, and a subprocess mini dry-run (8 placeholder devices — the full
512-device sweep runs via `python -m repro.launch.dryrun --all`)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.dryrun import parse_collective_bytes
from repro.launch.mesh import allocation_mesh_shape, mesh_from_allocation
from repro.parallel.sharding import (DEFAULT_RULES, param_logical_axes,
                                     rules_for, safe_spec, spec_for)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_collective_bytes():
    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = bf16[64]{0} all-gather(%y), dimensions={0}
  %nothing = f32[8]{0} add(%a, %b)
  %cp = (s32[4]{0}, s32[4]{0}) collective-permute(%p, %q)
"""
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"]["bytes"] == 128 * 256 * 4
    assert out["all-gather"]["bytes"] == 64 * 2
    assert out["collective-permute"]["bytes"] == 32
    assert out["total_count"] == 3


def test_rules_adapt_to_mesh_axes():
    mesh = jax.make_mesh((1,), ("data",))
    rules = rules_for(mesh)
    assert rules["heads"] is None        # no model axis
    assert rules["batch"] == "data"      # no pod axis
    assert spec_for(("batch", "seq"), rules) == P("data", None)


def test_safe_spec_divisibility():
    mesh = jax.make_mesh((1,), ("data",))
    rules = {"batch": "data", "heads": "data"}
    # dim 7 not divisible by 1? axis size 1 -> dropped (sz>1 required)
    assert safe_spec((7, 3), ("batch", None), mesh, rules) == P(None, None)


def test_param_logical_axes_moe_no_duplicate():
    params = {"moe": {"w_gate": jnp.zeros((160, 64, 32)),
                      "w_down": jnp.zeros((160, 32, 64)),
                      "router": jnp.zeros((64, 160))},
              "attn": {"w_q": jnp.zeros((64, 64))}}
    axes = param_logical_axes(params, n_expert_hint=160)
    def is_leaf(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)
    flat = jax.tree_util.tree_leaves(axes, is_leaf=is_leaf)
    for a in flat:
        resolved = [DEFAULT_RULES.get(n) if n else None for n in a]
        named = [r for r in resolved if isinstance(r, str)]
        assert len(named) == len(set(named)), a


def test_mesh_from_allocation_order():
    coords = [(0, 0, i) for i in range(len(jax.devices()))]
    n = len(coords)
    mesh = mesh_from_allocation(coords, (n, 1), ("data", "model"))
    assert mesh.shape == {"data": n, "model": 1}


def test_allocation_mesh_shape():
    d, m = allocation_mesh_shape(16)
    assert d * m == 16
    d, m = allocation_mesh_shape(24, prefer_model=6)
    assert (d, m) == (4, 6)


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    """Lower+compile a reduced config on an 8-device host mesh in a
    clean subprocess (dryrun.py owns XLA_FLAGS; tests must not)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.configs import get_config, smoke_variant
from repro.configs.shapes import InputShape, batch_specs
from repro.models import model as lm
from repro.parallel.sharding import (logical_rules, param_shardings,
                                     rules_for, batch_specs_sharding)
from repro.train.optim import OptimConfig, init_opt_state
from repro.train.train_step import train_step

cfg = smoke_variant(get_config("llama3-8b"))
mesh = jax.make_mesh((4, 2), ("data", "model"))
rules = rules_for(mesh)
shape = InputShape("mini", 64, 8, "train")
params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
ps = param_shardings(params, mesh, rules)
opt = jax.eval_shape(init_opt_state, params)
os_ = {"mu": ps, "nu": ps,
       "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
bs = batch_specs(cfg, shape)
bsh = batch_specs_sharding(bs, mesh, rules)
oc = OptimConfig()

def fn(p, o, b):
    with logical_rules(rules):
        np_, no, m = train_step(cfg, oc, p, o, b)
    return np_, no, m["loss"]

with mesh:
    lowered = jax.jit(fn, in_shardings=(ps, os_, bsh),
                      out_shardings=(ps, os_, None)).lower(params, opt, bs)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
print(json.dumps({"flops": cost.get("flops", -1),
                  "devices": len(jax.devices())}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    assert out["flops"] > 0


@pytest.mark.slow
def test_mini_dryrun_decode_subprocess():
    """serve_step lowers under a small mesh with sharded KV caches."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.configs import get_config, smoke_variant
from repro.configs.shapes import InputShape, batch_specs
from repro.models import model as lm
from repro.parallel.sharding import (logical_rules, param_shardings,
                                     rules_for, batch_specs_sharding,
                                     decode_state_specs)
from repro.serve import engine

cfg = smoke_variant(get_config("zamba2-1.2b"))
mesh = jax.make_mesh((4, 2), ("data", "model"))
rules = rules_for(mesh)
shape = InputShape("mini_dec", 64, 8, "decode")
params = jax.eval_shape(lambda: lm.init_model(cfg, jax.random.PRNGKey(0)))
ps = param_shardings(params, mesh, rules)
state = jax.eval_shape(lambda: engine.init_state(cfg, 8, 64))
ss = decode_state_specs(state, mesh, rules)
bs = batch_specs(cfg, shape)
bsh = batch_specs_sharding(bs, mesh, rules)

def fn(p, s, b):
    with logical_rules(rules):
        return engine.serve_step(cfg, p, s, b)

with mesh:
    compiled = jax.jit(fn, in_shardings=(ps, ss, bsh),
                       out_shardings=(None, ss)).lower(
        params, state, bs).compile()
print(json.dumps({"ok": True}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
