"""Multi-device parity (8 CPU host devices via subprocess): the sharded
system == the single-device reference, and the LP collective-halving claim
is visible in the compiled HLO."""
import json

import pytest

from _helpers import run_multidevice

pytestmark = pytest.mark.slow


def test_tp_dp_fsdp_parity():
    """One subprocess checks: (a) TPxDP shard_map == single device,
    (b) FSDP == single device, (c) pod-compressed grads stay close."""
    out = run_multidevice(r"""
import jax, jax.numpy as jnp, json
from jax.sharding import NamedSharding
from repro.configs import get_config, reduced_config
from repro.core.lp import plan_range
from repro.model import transformer as T
from repro.parallel.context import ParallelContext, make_context
from repro.train import TrainConfig, OptConfig, init_state, make_train_step, make_sharded_train_step
from repro.train.trainer import state_pspecs

cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=4)
plan = plan_range(cfg, 1, 3)
tc = TrainConfig(opt=OptConfig(lr=3e-3, warmup_steps=2, total_steps=40))
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}
babs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)

ms1 = T.build_structure(cfg, plan=plan, tp=1)
st1 = init_state(ms1, jax.random.PRNGKey(0), ParallelContext(), tc)
step1 = jax.jit(make_train_step(ms1, ParallelContext(), tc))
for _ in range(3):
    st1, m1 = step1(st1, batch)

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
losses = {}
for name, kw in [("tp", dict(fsdp=False)),
                 ("fsdp", dict(fsdp=True, fsdp_data=2))]:
    ms2 = T.build_structure(cfg, plan=plan, tp=2, **kw)
    pc2 = make_context(mesh, sp=True)
    st2 = jax.device_put(init_state(ms2, jax.random.PRNGKey(0), pc2, tc),
        jax.tree.map(lambda s: NamedSharding(mesh, s), state_pspecs(ms2, pc2, tc)))
    fn, _, bspec, _ = make_sharded_train_step(ms2, mesh, tc, babs, donate=False)
    bsh = jax.device_put(batch, jax.tree.map(lambda s: NamedSharding(mesh, s), bspec))
    for _ in range(3):
        st2, m2 = fn(st2, bsh)
    losses[name] = float(m2["loss"])

tc3 = TrainConfig(opt=OptConfig(lr=3e-3, warmup_steps=2, total_steps=40), compress_pod=True)
ms3 = T.build_structure(cfg, plan=plan, tp=2)
pc3 = make_context(mesh, sp=True)
st3 = jax.device_put(init_state(ms3, jax.random.PRNGKey(0), pc3, tc3),
    jax.tree.map(lambda s: NamedSharding(mesh, s), state_pspecs(ms3, pc3, tc3)))
fn3, _, bspec3, _ = make_sharded_train_step(ms3, mesh, tc3, babs, donate=False)
bsh = jax.device_put(batch, jax.tree.map(lambda s: NamedSharding(mesh, s), bspec3))
for _ in range(3):
    st3, m3 = fn3(st3, bsh)
losses["compressed"] = float(m3["loss"])
losses["ref"] = float(m1["loss"])
print("RESULT " + json.dumps(losses))
""")
    res = json.loads([l for l in out.splitlines() if l.startswith("RESULT")][0][7:])
    assert abs(res["tp"] - res["ref"]) < 2e-3, res
    assert abs(res["fsdp"] - res["ref"]) < 2e-3, res
    assert abs(res["compressed"] - res["ref"]) < 5e-2, res


def test_lp_halves_allreduce_count_in_hlo():
    """THE paper claim, structurally: over the paired range, the decode step
    needs half the all-reduces. Count them in the compiled HLO."""
    out = run_multidevice(r"""
import jax, jax.numpy as jnp, json, re
from repro.configs import get_config, reduced_config
from repro.core.lp import LPPlan
from repro.model import transformer as T
from repro.model import stack as STK
from repro.serve.engine import ServeConfig, make_sharded_serve_step
from repro.analysis.roofline import collective_bytes

cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=8)
mesh = jax.make_mesh((1, 2), ("data", "model"))
counts = {}
STK.set_scan_unroll(True)
for name, plan in [("vanilla", LPPlan(())),
                   ("lp", LPPlan(((0,1),(2,3),(4,5),(6,7))))]:
    ms = T.build_structure(cfg, plan=plan, tp=2)
    sv = ServeConfig(max_len=64, kv_mode="heads")
    fn, c_abs, c_specs, pc = make_sharded_serve_step(ms, mesh, sv, batch=4)
    import repro.launch.specs as SP
    p_abs = jax.tree.map(lambda pd: jax.ShapeDtypeStruct(pd.shape, jnp.float32),
                         T.model_template(ms), is_leaf=lambda x: hasattr(x, "pspec"))
    tok = jax.ShapeDtypeStruct((4,), jnp.int32)
    t = jax.ShapeDtypeStruct((), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    lowered = fn.lower(p_abs, tok, c_abs, t, key)
    txt = lowered.compile().as_text()
    coll = collective_bytes(txt)
    counts[name] = int(coll.get("count:all-reduce", 0))
print("RESULT " + json.dumps(counts))
""")
    res = json.loads([l for l in out.splitlines() if l.startswith("RESULT")][0][7:])
    vanilla, lp = res["vanilla"], res["lp"]
    # 8 layers x 2 ARs -> 4 pairs x 2 ARs: difference must be exactly 8
    assert vanilla - lp == 8, res


def test_pipeline_parallel_matches_sequential():
    out = run_multidevice(r"""
import jax, jax.numpy as jnp, json
from jax.sharding import PartitionSpec as P
from repro.parallel.pp import pipeline_apply, stage_slice

mesh = jax.make_mesh((4,), ("pipe",))
n_stages, n_layers, d = 4, 8, 16
ws = jax.random.normal(jax.random.PRNGKey(0), (n_layers, d, d)) * (d ** -0.5)

def seq_ref(x):
    for i in range(n_layers):
        x = jnp.tanh(x @ ws[i])
    return x

x_micro = jax.random.normal(jax.random.PRNGKey(1), (8, 2, d))

def stage_fn(params, x):
    for w in params:
        x = jnp.tanh(x @ w)
    return x

def run(x_micro):
    stage = jax.lax.axis_index("pipe")
    # static per-stage params: slice with dynamic_slice over the stacked tree
    lo0, hi0 = stage_slice(n_layers, n_stages, 0)
    k = hi0 - lo0
    params = jax.lax.dynamic_slice_in_dim(ws, stage * k, k, axis=0)
    return pipeline_apply(lambda p, x: stage_fn(p, x), params, x_micro,
                          axis="pipe", n_stages=n_stages)

f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P(), out_specs=P(),
                          check_vma=False))
out = f(x_micro)
ref = jax.vmap(seq_ref)(x_micro)
print("RESULT", float(jnp.abs(out - ref).max()))
""")
    err = float([l for l in out.splitlines() if l.startswith("RESULT")][0].split()[1])
    assert err < 1e-5


def test_sp_on_off_equal():
    """Sequence parallelism is a pure re-decomposition: same math."""
    out = run_multidevice(r"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, reduced_config
from repro.core.lp import plan_range
from repro.model import transformer as T
from repro.parallel.context import make_context

cfg = reduced_config(get_config("tinyllama-1.1b"), n_layers=4)
plan = plan_range(cfg, 0, 4)
mesh = jax.make_mesh((2, 4), ("data", "model"))
ms = T.build_structure(cfg, plan=plan, tp=4)
params = T.init_params(ms, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
outs = []
for sp in (False, True):
    pc = make_context(mesh, sp=sp)
    def fwd(p, tk):
        lg, _, _ = T.forward_full(p, tk, ms=ms, pc=pc)
        return lg
    f = jax.jit(jax.shard_map(fwd, mesh=mesh,
        in_specs=(T.param_pspecs(ms), P("data", None)),
        out_specs=P("data", None, "model"), check_vma=False))
    outs.append(f(params, toks))
import numpy as np
print("RESULT", float(jnp.abs(outs[0] - outs[1]).max()))
""")
    err = float([l for l in out.splitlines() if l.startswith("RESULT")][0].split()[1])
    assert err < 2e-4
