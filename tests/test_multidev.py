"""Multi-device behaviour via subprocess (XLA forced host devices).

Covers: SPLIT/MERGE on a real 2-pod fabric, reshard-on-mode-switch, ring
collectives vs oracles, q8 all-reduce, elastic pod-failure shrink, a
small-mesh multi-pod dry-run of REDUCED configs for every arch family, and
the split/merge SERVING cluster (bit-identity vs the single-device engine,
mid-stream reconfigure, router fairness) under 2 and 4 forced host devices.
Grouped into few subprocess scripts to amortize interpreter startup.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr[-4000:]}"
    return out.stdout


@pytest.mark.slow
def test_cluster_modes_collectives_elastic():
    out = run_py(
        r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import SpatzformerCluster, Mode, switch_mode, reshard
from repro.dist.collectives import ring_rs_matmul, ring_ag_matmul
from repro.dist.compression import ring_allreduce_q8

# ---- cluster views
cl = SpatzformerCluster(n_pods=2)
assert cl.n_devices == 8
mi = cl.merge_info(); si = cl.split_infos()
assert mi.data_size == 4 and mi.model_size == 2
assert len(si) == 2 and si[0].n_devices == 4

# ---- reshard on mode switch preserves values
x = jnp.arange(64.0).reshape(8, 8)
state = jax.device_put({"w": x}, si[0].named(P("data", None)))
merged, rep = switch_mode(cl, Mode.MERGE, state)
np.testing.assert_array_equal(np.asarray(merged["w"]), np.asarray(x))
assert rep.bytes_moved == 64 * 4

# ---- elastic shrink
surv = cl.surviving_cluster(dead_pod=0)
assert surv.n_devices == 4
shrunk = reshard(merged, surv.pod_info(0))
np.testing.assert_array_equal(np.asarray(shrunk["w"]), np.asarray(x))

# ---- ring collectives on 4-way axis
mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("model",))
rng = np.random.default_rng(0)
a = rng.standard_normal((16, 32)).astype(np.float32)
w = rng.standard_normal((32, 24)).astype(np.float32)
f = jax.jit(jax.shard_map(lambda xl, wl: ring_rs_matmul(xl, wl, "model"),
    mesh=mesh, in_specs=(P(None, "model"), P("model", None)), out_specs=P("model", None)))
np.testing.assert_allclose(np.asarray(f(a, w)), a @ w, rtol=2e-4, atol=2e-4)
g = jax.jit(jax.shard_map(lambda xl, wl: ring_ag_matmul(xl, wl, "model"),
    mesh=mesh, in_specs=(P("model", None), P(None, "model")), out_specs=P(None, "model")))
np.testing.assert_allclose(np.asarray(g(a, w)), a @ w, rtol=2e-4, atol=2e-4)
vals = rng.standard_normal((4, 64)).astype(np.float32)
h = jax.jit(jax.shard_map(lambda v: ring_allreduce_q8(v[0], "model")[None],
    mesh=mesh, in_specs=(P("model", None),), out_specs=P("model", None)))
err = np.abs(np.asarray(h(vals)) - vals.mean(0)[None]).max()
assert err < 0.05 * np.abs(vals.mean(0)).max() + 1e-3
print("MULTIDEV-CORE-OK")
"""
    )
    assert "MULTIDEV-CORE-OK" in out


@pytest.mark.slow
def test_small_mesh_multipod_dryrun_reduced_archs():
    """Reduced config per family × (2,2,2) multi-pod mesh: lower+compile the
    train step — the structural multi-pod check at test scale."""
    out = run_py(
        r"""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, AxisType
from repro.configs import get_arch, TrainConfig
from repro.dist.sharding import MeshInfo, batch_shardings, param_shardings, replicated
from repro.models import LM
from repro.models.model import input_specs
from repro.train import adamw_init, make_train_step

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,)*3)
info = MeshInfo(mesh, batch_axes=("pod", "data"))
for name in ["codeqwen1.5-7b", "deepseek-v2-lite-16b", "falcon-mamba-7b", "zamba2-2.7b", "musicgen-large"]:
    cfg = get_arch(name).reduced()
    model = LM(cfg, mesh_info=info)
    params_s = model.param_specs()
    p_sh = param_shardings(params_s, info)
    batch_s = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
               "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    if cfg.modality == "audio":
        batch_s = {"embeds": jax.ShapeDtypeStruct((8, 32, cfg.d_model), jnp.float32),
                   "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
    b_sh = batch_shardings(batch_s, info)
    step = make_train_step(model, TrainConfig())
    opt_s = jax.eval_shape(lambda: adamw_init(params_s))
    o_sh = param_shardings(opt_s, info)
    m_sh = {k: replicated(info) for k in ("loss", "aux", "grad_norm", "lr")}
    with mesh:
        compiled = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                           out_shardings=(p_sh, o_sh, m_sh)).lower(params_s, opt_s, batch_s).compile()
    assert compiled.cost_analysis() is not None
    print("OK", name)
print("MULTIDEV-DRYRUN-OK")
"""
    )
    assert "MULTIDEV-DRYRUN-OK" in out


@pytest.mark.slow
def test_serve_cluster_split_merge_2dev():
    """2 forced host devices: the serving cluster's split mode (2 pinned
    replicas + router) and merge mode (one 2-way tensor-parallel engine,
    heads sharded) both serve the same greedy mixed stream BIT-IDENTICAL to
    a plain single-device engine — including the ragged chunked-prefill
    tier and a mid-stream reconfigure (drain → re-home → resume)."""
    out = run_py(
        r"""
import repro  # noqa: F401
import numpy as np, jax
from repro.configs import get_arch
from repro.core.modes import Mode
from repro.models import LM
from repro.serve import Request, SamplingParams, ServeCluster, ServeEngine

assert jax.device_count() == 2
cfg = get_arch("codeqwen1.5-7b").reduced()
m = LM(cfg)
p = m.init(jax.random.key(0))

def stream(seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=s).astype(np.int32),
                    params=SamplingParams(max_new=6))
            for i, s in enumerate((5, 23, 11, 31, 8, 17, 26, 3))]

eng = ServeEngine(m, p, batch_slots=3, max_len=64)
for r in stream(): eng.submit(r)
eng.run()
ref = {r.rid: r.generated for r in eng.finished}

cl = ServeCluster(m, p, mode=Mode.SPLIT, batch_slots=3, max_len=64)
assert cl.n_replicas == 2
for r in stream(): cl.submit(r)
cl.run()
assert {r.rid: r.generated for r in cl.finished} == ref, "split != single"

rep = cl.reconfigure(Mode.MERGE)
assert not rep.cached and rep.bytes_moved > 0
assert cl.engines[0].backend.mesh_info.model_size == 2
cl.finished.clear()
for r in stream(): cl.submit(r)
cl.run()
assert {r.rid: r.generated for r in cl.finished} == ref, "merge != single"

# chunked ragged tier under TP: tight budget forces packed prefills
cl2 = ServeCluster(m, p, mode=Mode.MERGE, batch_slots=3, max_len=64,
                   prefill_budget=5)
for r in stream(): cl2.submit(r)
cl2.run()
assert {r.rid: r.generated for r in cl2.finished} == ref, "merge chunked != single"

# mid-stream reconfigure: drain at t, re-home, resume
cl.finished.clear()
arrivals = [(i * 0.002, r) for i, r in enumerate(stream())]
st = cl.run(arrivals=arrivals, reconfigure_schedule=[(0.006, Mode.SPLIT)])
assert {r.rid: r.generated for r in cl.finished} == ref, "mid-stream != single"
assert len(st.reconfigures) == 1 and st.reconfigures[0].cached
assert st.mode == "merge->split"
print("CLUSTER-2DEV-OK")
""",
        devices=2,
    )
    assert "CLUSTER-2DEV-OK" in out


@pytest.mark.slow
def test_serve_cluster_router_fairness_4dev():
    """4 forced host devices: JSQ spreads uniform tenant-less traffic
    evenly over 4 replicas; tenant affinity keeps each tenant on one
    replica while distinct tenants spread; outputs stay bit-identical to
    the single-device engine; 4-way TP merge serves the same stream."""
    out = run_py(
        r"""
import repro  # noqa: F401
import numpy as np, jax
from repro.configs import get_arch
from repro.core.modes import Mode
from repro.models import LM
from repro.serve import Request, SamplingParams, ServeCluster, ServeEngine

assert jax.device_count() == 4
cfg = get_arch("codeqwen1.5-7b").reduced()
m = LM(cfg)
p = m.init(jax.random.key(0))

def stream(tenants=None, n=12, seed=31):
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, size=8).astype(np.int32),
                    params=SamplingParams(max_new=4),
                    tenant=None if tenants is None else tenants[i % len(tenants)])
            for i in range(n)]

eng = ServeEngine(m, p, batch_slots=2, max_len=32)
for r in stream(): eng.submit(r)
eng.run()
ref = {r.rid: r.generated for r in eng.finished}

# fairness: 12 uniform requests over 4 replicas -> 3 each
cl = ServeCluster(m, p, mode=Mode.SPLIT, batch_slots=2, max_len=32)
assert cl.n_replicas == 4
for r in stream(): cl.submit(r)
cl.run()
assert cl.router.assigned == [3, 3, 3, 3], cl.router.assigned
assert {r.rid: r.generated for r in cl.finished} == ref, "split != single"

# tenant affinity: each tenant pinned to one replica, tenants spread
cl2 = ServeCluster(m, p, mode=Mode.SPLIT, batch_slots=2, max_len=32)
tenants = ["a", "b", "c", "d"]
routed = {}
for r in stream(tenants=tenants):
    routed.setdefault(r.tenant, set()).add(cl2.submit(r).replica)
cl2.run()
assert all(len(v) == 1 for v in routed.values()), routed
assert len(set(next(iter(v)) for v in routed.values())) == 4, routed
assert {r.rid: r.generated for r in cl2.finished} == ref, "tenants != single"

# 4-way TP merge on the same stream
rep = cl.reconfigure(Mode.MERGE)
assert cl.engines[0].backend.mesh_info.model_size == 4
cl.finished.clear()
for r in stream(): cl.submit(r)
cl.run()
assert {r.rid: r.generated for r in cl.finished} == ref, "merge != single"
print("CLUSTER-4DEV-OK")
""",
        devices=4,
    )
    assert "CLUSTER-4DEV-OK" in out
