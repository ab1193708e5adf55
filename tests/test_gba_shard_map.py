"""shard_map GBA (explicit psum of decayed per-worker grads) must equal
the functional aggregate_dense reference, and the sharded fused flat
path (core.flat_sharded) must be bit-exact with the per-leaf chain and
the single-host flat path.  Everything runs in subprocesses with forced
host devices (device count locks at first jax init); the sharded-flat
cases share ONE 4-device subprocess via a module fixture so the suite
pays the jax import + compiles once."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest


def _run_forced(script: str, timeout: int = 540) -> dict:
    # JAX_PLATFORMS=cpu matters: without it jax probes for accelerator
    # plugins and the probe timeouts dwarf the actual test (minutes vs
    # seconds).  The scripts force host-platform devices anyway.
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"},
        cwd=Path(__file__).resolve().parents[1], timeout=timeout)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core import aggregate_dense
from repro.core.gba_shard_map import make_gba_psum_step
from repro.optim import sgd

from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("data",))
M = 8
D = 16

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)

key = jax.random.PRNGKey(0)
params = {"w": jax.random.normal(key, (D,))}
batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (32, D)),
         "y": jax.random.normal(jax.random.PRNGKey(2), (32,))}
tokens = jnp.array([5, 5, 4, 1, 5, 0, 5, 3], jnp.int32)  # workers' tokens
gstep = jnp.int32(5)
IOTA = 2

opt = sgd(0.1)
state = opt.init(params)
with mesh:
    step = make_gba_psum_step(mesh, loss_fn, opt, IOTA)
    batch_sharded = jax.device_put(batch, NamedSharding(mesh, P("data")))
    tokens_sharded = jax.device_put(tokens, NamedSharding(mesh, P("data")))
    new_params, _, loss = jax.jit(step)(params, state, batch_sharded,
                                        tokens_sharded, gstep)

# reference: per-worker grads aggregated with aggregate_dense
def worker_grads(params):
    gs = []
    for i in range(M):
        shard = {k: v[i * 4:(i + 1) * 4] for k, v in batch.items()}
        gs.append(jax.grad(loss_fn)(params, shard))
    return jax.tree.map(lambda *x: jnp.stack(x), *gs)

agg = aggregate_dense(worker_grads(params), tokens, gstep, iota=IOTA)
ref_params, _ = opt.update(params, agg, opt.init(params))
err = float(jnp.max(jnp.abs(new_params["w"] - ref_params["w"])))
print(json.dumps({"err": err, "devices": jax.device_count()}))
"""


@pytest.mark.slow
def test_shard_map_gba_matches_reference():
    """Marked slow: spawns a fresh 8-device jax process whose jit compile
    alone runs minutes on a loaded CPU container (scripts/ci.sh budget)."""
    res = _run_forced(_SCRIPT, timeout=300)
    assert res["devices"] == 8
    assert res["err"] < 1e-5, res


# ---------------------------------------------------------------------------
# sharded fused flat apply (core.flat_sharded): one subprocess, many checks
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import functools
import json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.core.flat_sharded import (ShardedFlatLayout,
                                     init_sharded_flat_buffer,
                                     per_leaf_kernel_apply,
                                     sharded_flat_push_and_maybe_apply)
from repro.core.gba import (init_flat_buffer,
                            flat_buffer_push_and_maybe_apply,
                            init_buffer, buffer_push_and_maybe_apply)
from repro.core.gba_shard_map import (make_gba_fused_psum_step,
                                      make_gba_psum_step)
from repro.distributed import sharding as S
from repro.optim import adagrad

out = {"devices": jax.device_count()}
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
key = jax.random.PRNGKey(7)
# non-tile-multiple leaf sizes on purpose: 297, 41, 700 against tile=256
params = {"w": jax.random.normal(key, (33, 9)),
          "b": {"c": jax.random.normal(jax.random.PRNGKey(8), (41,)),
                "d": jax.random.normal(jax.random.PRNGKey(9), (700,))}}
m, iota, lr = 4, 2, 0.05
tokens = [0, 4, 5, 5]
grads = [jax.tree.map(
    lambda p, i=i: jax.random.normal(jax.random.PRNGKey(100 + i), p.shape),
    params) for i in range(m)]

# --- sharded fused path: ONE jitted push/apply step, executed m times ------
layout, buf = init_sharded_flat_buffer(params, m, 4, tile=256)
out["shard_size"] = layout.shard_size
out["padded_total"] = layout.padded_total
specs = S.flat_slice_specs(layout, mesh, "data")
pf = jax.device_put(layout.ravel(params), NamedSharding(mesh, specs["flat"]))
af = jax.device_put(jnp.full((layout.padded_total,), 0.1, jnp.float32),
                    NamedSharding(mesh, specs["flat"]))
buf = jax.device_put(buf, jax.tree.map(
    lambda s: NamedSharding(mesh, s), specs["buffer"],
    is_leaf=lambda s: isinstance(s, P)))

@jax.jit
def push(buf, g, tok, pf, af):
    return sharded_flat_push_and_maybe_apply(
        buf, g, tok, pf, af, lr, mesh=mesh, layout=layout, iota=iota)

p0 = layout.ravel(params)
noop_err, applied_flags = 0.0, []
with mesh:
    for i in range(m):
        pf, af, applied, buf = push(buf, layout.ravel(grads[i]),
                                    jnp.int32(tokens[i]), pf, af)
        applied_flags.append(bool(applied))
        if i < m - 1:  # partial buffer: params must pass through untouched
            noop_err = max(noop_err, float(jnp.max(jnp.abs(pf - p0))))
out["applied"] = applied_flags
out["noop_err"] = noop_err
sharded = jax.tree.leaves(layout.unravel(pf))

# --- single-host flat path on the same pushes ------------------------------
flayout, fbuf = init_flat_buffer(params, m)

@jax.jit
def push1(buf, g, tok, pf, af):
    return flat_buffer_push_and_maybe_apply(buf, g, tok, pf, af, lr,
                                            iota=iota)

pf1 = flayout.ravel(params)
af1 = jnp.full((flayout.total,), 0.1, jnp.float32)
for i in range(m):
    pf1, af1, _, fbuf = push1(fbuf, flayout.ravel(grads[i]),
                              jnp.int32(tokens[i]), pf1, af1)
out["err_flat"] = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                      zip(sharded, jax.tree.leaves(flayout.unravel(pf1))))

# --- per-leaf kernel chain: one gba_apply launch per leaf slice ------------
pl_p, _ = jax.jit(functools.partial(per_leaf_kernel_apply, layout,
                                    iota=iota))(
    layout.ravel(params),
    jnp.full((layout.padded_total,), 0.1, jnp.float32),
    jnp.stack([layout.ravel(g) for g in grads]),
    jnp.asarray(tokens, jnp.int32), jnp.int32(0), lr)
out["err_leaf_kernel"] = max(
    float(jnp.max(jnp.abs(a - b))) for a, b in
    zip(sharded, jax.tree.leaves(layout.unravel(pl_p))))

# --- per-leaf XLA chain (buffer_push_and_maybe_apply + adagrad) ------------
opt = adagrad(lr)

@jax.jit
def chain_push(pbuf, g, tok, params, ostate):
    def apply_fn(agg):
        return opt.update(params, agg, ostate)
    def noop_fn():
        return params, ostate
    return buffer_push_and_maybe_apply(pbuf, g, tok, iota, apply_fn,
                                       noop_fn)

cur_p, cur_o = params, opt.init(params)
pbuf = init_buffer(params, m)
for i in range(m):
    (cur_p, cur_o), pbuf = chain_push(pbuf, grads[i], jnp.int32(tokens[i]),
                                      cur_p, cur_o)
out["err_leaf_xla"] = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                          zip(sharded, jax.tree.leaves(cur_p)))

# --- fused psum step vs per-leaf psum step + adagrad -----------------------
D = 16
def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)

wparams = {"w": jax.random.normal(key, (D,)), "b": jnp.zeros(())}
batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (32, D)),
         "y": jax.random.normal(jax.random.PRNGKey(2), (32,))}
wtokens = jnp.array([5, 4, 1, 5], jnp.int32)
gstep = jnp.int32(5)
wlayout = ShardedFlatLayout.from_params(wparams, 4, tile=64)
with mesh:
    step = make_gba_fused_psum_step(mesh, loss_fn, wlayout, iota=iota,
                                    lr=0.1)
    wspecs = S.flat_slice_specs(wlayout, mesh, "data")
    wpf = jax.device_put(wlayout.ravel(wparams),
                         NamedSharding(mesh, wspecs["flat"]))
    waf = jax.device_put(
        jnp.full((wlayout.padded_total,), 0.1, jnp.float32),
        NamedSharding(mesh, wspecs["flat"]))
    bsh = jax.device_put(batch, NamedSharding(mesh, P("data")))
    tsh = jax.device_put(wtokens, NamedSharding(mesh, P("data")))
    new_pf, _, loss = jax.jit(step)(wpf, waf, bsh, tsh, gstep)
fused = jax.tree.leaves(wlayout.unravel(new_pf))

wopt = adagrad(0.1)  # same accum init (0.1) / eps as the fused kernel
with mesh:
    ref_step = make_gba_psum_step(mesh, loss_fn, wopt, iota)
    ref_params, _, ref_loss = jax.jit(ref_step)(
        wparams, wopt.init(wparams), bsh, tsh, gstep)
out["psum_err"] = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                      zip(fused, jax.tree.leaves(ref_params)))
out["psum_loss_err"] = abs(float(loss) - float(ref_loss))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_results():
    return _run_forced(_SHARDED_SCRIPT)


def test_sharded_flat_apply_parity_4dev(sharded_results):
    """Tentpole acceptance: on a forced 4-device host mesh, the sharded
    fused apply (one gba_apply launch per PS shard) is bit-exact with
    the single-host flat path, with the per-leaf kernel chain (one
    launch per leaf), and with the per-leaf XLA aggregate+Adagrad chain
    — on non-tile-multiple leaf sizes.  The XLA-chain bound is kept at
    last-ulp tolerance because its reduction order is compiler-chosen."""
    res = sharded_results
    assert res["devices"] == 4
    assert res["padded_total"] == 4 * res["shard_size"]
    assert res["err_flat"] == 0.0, res         # bit-exact: same kernel math
    assert res["err_leaf_kernel"] == 0.0, res  # bit-exact: per-leaf launches
    assert res["err_leaf_xla"] < 1e-6, res


def test_sharded_flat_partial_buffer_noop(sharded_results):
    """The partial-buffer branch is a strict no-op: the first M-1 pushes
    leave params untouched bit-for-bit, the M-th applies."""
    res = sharded_results
    assert res["applied"] == [False, False, False, True]
    assert res["noop_err"] == 0.0, res


def test_fused_psum_step_matches_per_leaf_psum_step(sharded_results):
    """make_gba_fused_psum_step (all_gather params -> per-worker grads ->
    all_to_all into the (M, shard) buffer -> one gba_apply per shard)
    must match make_gba_psum_step + Adagrad; only the scalar loss is
    psum'd."""
    res = sharded_results
    assert res["psum_err"] < 1e-6, res
    assert res["psum_loss_err"] < 1e-6, res
