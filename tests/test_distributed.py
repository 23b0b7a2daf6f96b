"""Distributed paths that need multiple (placeholder) devices run in a
subprocess so the 1-device main test session stays clean."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_dist_hck_matvec_and_cg():
    """shard_map distributed HCK == dense oracle of the composed kernel."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.kernels_fn import BaseKernel
from repro.launch import dist_hck

P_DEV, n_local, d, rank = 8, 64, 4, 8
ker = BaseKernel("gaussian", sigma=2.0, jitter=1e-6)
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (P_DEV * n_local, d))
local_fs = [dist_hck.build_local_factors(
    x[i*n_local:(i+1)*n_local], kernel=ker, rank=rank, local_levels=2,
    key=jax.random.fold_in(key, i)) for i in range(P_DEV)]
root_lms = jnp.stack([f.landmarks[0][0] for f in local_fs])
top = dist_hck.build_top_factors(root_lms, kernel=ker, key=jax.random.PRNGKey(7))
A = dist_hck.dist_to_dense(local_fs, top)
assert float(jnp.linalg.eigvalsh(A).min()) > 0
b = jax.random.normal(jax.random.PRNGKey(3), (P_DEV * n_local, 1))
mv = dist_hck.make_dist_matvec("dev")
mesh = jax.make_mesh((P_DEV,), ("dev",))
stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *local_fs)
def body(local_f, top, b_local):
    local_f = jax.tree.map(lambda a: a[0], local_f)
    return mv(local_f, top, b_local[0])[None]
from jax.experimental.shard_map import shard_map
sm = shard_map(body, mesh=mesh, in_specs=(P("dev"), P(), P("dev")),
               out_specs=P("dev"))
y = jax.jit(sm)(stacked, top, b.reshape(P_DEV, n_local, 1))
err = float(jnp.max(jnp.abs(y.reshape(-1, 1) - A @ b)))
assert err < 1e-3, err
def gmv(v):
    return jax.jit(sm)(stacked, top, v.reshape(P_DEV, n_local, 1)).reshape(-1)
xs = dist_hck.dist_solve(gmv, b[:, 0], ridge=0.5, iters=80)
xr = jnp.linalg.solve(A + 0.5*jnp.eye(A.shape[0]), b[:, 0])
assert float(jnp.max(jnp.abs(xs - xr))) < 1e-3
print("DIST_OK")
"""
    assert "DIST_OK" in _run(code)


@pytest.mark.slow
def test_dryrun_single_cell_compiles():
    """The multi-pod dry-run machinery itself: one decode cell on the
    2x16x16 mesh must lower + compile (compile-only, no cost probes)."""
    code = """
from repro.launch.dryrun import dryrun_cell
rec = dryrun_cell("granite-3-2b", "decode_32k", multi_pod=True,
                  skip_cost=True, verbose=False)
assert rec["ok"], rec.get("error")
assert rec["memory"]["argument_bytes"] > 0
print("DRYRUN_OK", rec["memory"]["argument_bytes"])
"""
    # dryrun module sets its own 512-device XLA_FLAGS at import
    out = _run(code, devices=512, timeout=560)
    assert "DRYRUN_OK" in out


@pytest.mark.slow
def test_sharded_train_step_multidevice():
    """The real train step under an (2, 4) mesh on 8 host devices: params
    FSDP+TP sharded, batch DP sharded — executes (not just compiles)."""
    code = """
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch, TrainConfig
from repro.configs.base import MeshConfig
from repro.models.transformer import init_params, param_pspecs
from repro.models.layers import axis_rules
from repro.training.train_loop import make_train_step
from repro.training import optimizer as opt
from repro.data.pipeline import TokenPipeline

cfg = get_arch("granite-3-2b").reduced()
mesh = jax.make_mesh((2, 4), ("data", "model"))
mcfg = MeshConfig(data=2, model=4, pods=1)
params = init_params(cfg, jax.random.PRNGKey(0))
pspecs = param_pspecs(cfg, mcfg)
param_sh = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
params = jax.tree.map(jax.device_put, params, param_sh)
state = opt.init_opt_state(params)
tcfg = TrainConfig(lr=1e-3, warmup_steps=1, total_steps=5)
pipe = TokenPipeline(vocab=cfg.vocab, seq_len=32, global_batch=4)
batch = pipe.batch_at(0)
batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
step = jax.jit(make_train_step(cfg, tcfg))
with mesh:
    with axis_rules(("data",)):
        params, state, metrics = step(params, state, batch)
loss = float(metrics["loss"])
assert loss == loss and loss < 20  # finite
print("SHARDED_TRAIN_OK", loss)
"""
    assert "SHARDED_TRAIN_OK" in _run(code)


@pytest.mark.slow
def test_dist_cg_preconditioner_accelerates():
    """The local Algorithm-2 inverse as a CG preconditioner: fewer
    iterations to a given residual than plain CG (the distributed-KRR
    solver path in launch/dist_hck.py)."""
    code = """
import jax, jax.numpy as jnp
from repro.core.kernels_fn import BaseKernel
from repro.core import hmatrix
from repro.launch import dist_hck

P_DEV, n_local, rank = 4, 128, 16
ker = BaseKernel("gaussian", sigma=1.0, jitter=1e-5)
key = jax.random.PRNGKey(0)
x = jax.random.uniform(key, (P_DEV * n_local, 4))
local_fs = [dist_hck.build_local_factors(
    x[i*n_local:(i+1)*n_local], kernel=ker, rank=rank, local_levels=2,
    key=jax.random.fold_in(key, i)) for i in range(P_DEV)]
root_lms = jnp.stack([f.landmarks[0][0] for f in local_fs])
top = dist_hck.build_top_factors(root_lms, kernel=ker, key=jax.random.PRNGKey(7))
A = dist_hck.dist_to_dense(local_fs, top)
ridge = 0.05
b = jax.random.normal(jax.random.PRNGKey(3), (A.shape[0],))

def mv(v):
    return A @ v

# block-diagonal local preconditioner from the per-device Algorithm-2 inverse
invs = [hmatrix.invert(f, ridge) for f in local_fs]
def precond(r):
    parts = [hmatrix.apply_inverse(inv, r[i*n_local:(i+1)*n_local][:, None])[:, 0]
             for i, inv in enumerate(invs)]
    return jnp.concatenate(parts)

xref = jnp.linalg.solve(A + ridge * jnp.eye(A.shape[0]), b)
def err_after(iters, pc):
    xs = dist_hck.dist_solve(mv, b, ridge=ridge, iters=iters, precond=pc)
    return float(jnp.linalg.norm(xs - xref) / jnp.linalg.norm(xref))

e_plain = err_after(8, None)
e_pc = err_after(8, precond)
print("plain:", e_plain, "precond:", e_pc)
assert e_pc < e_plain, (e_pc, e_plain)
print("PRECOND_OK")
"""
    assert "PRECOND_OK" in _run(code, devices=4)


@pytest.mark.slow
def test_elastic_restart_different_device_count():
    """Fault-tolerance: a checkpoint written under a 4-device mesh restores
    and keeps training under an 8-device mesh (elastic re-shard: global
    shapes + device_put with the new shardings)."""
    import tempfile

    ckdir = tempfile.mkdtemp()
    save_code = f"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch, TrainConfig
from repro.configs.base import MeshConfig
from repro.models.transformer import init_params, param_pspecs
from repro.models.layers import axis_rules
from repro.training.train_loop import make_train_step
from repro.training import optimizer as opt
from repro.training.checkpoint import CheckpointManager
from repro.data.pipeline import TokenPipeline

cfg = get_arch("granite-3-2b").reduced()
mesh = jax.make_mesh((2, 2), ("data", "model"))
mcfg = MeshConfig(data=2, model=2)
params = init_params(cfg, jax.random.PRNGKey(0))
pspecs = param_pspecs(cfg, mcfg)
sh = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                  is_leaf=lambda x: isinstance(x, P))
params = jax.tree.map(jax.device_put, params, sh)
state = opt.init_opt_state(params)
tcfg = TrainConfig(lr=1e-3, warmup_steps=1, total_steps=10)
pipe = TokenPipeline(vocab=cfg.vocab, seq_len=16, global_batch=2)
step = jax.jit(make_train_step(cfg, tcfg))
with mesh:
    with axis_rules(("data",)):
        params, state, m = step(params, state, pipe.batch_at(0))
CheckpointManager("{ckdir}").save(0, {{"params": params, "opt": state}})
print("SAVED", float(m["loss"]))
"""
    out = _run(save_code, devices=4)
    assert "SAVED" in out

    restore_code = f"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_arch, TrainConfig
from repro.configs.base import MeshConfig
from repro.models.transformer import init_params, param_pspecs
from repro.models.layers import axis_rules
from repro.training.train_loop import make_train_step
from repro.training import optimizer as opt
from repro.training.checkpoint import CheckpointManager, reshard_restore
from repro.data.pipeline import TokenPipeline

assert jax.device_count() == 8
cfg = get_arch("granite-3-2b").reduced()
mesh = jax.make_mesh((2, 4), ("data", "model"))   # DIFFERENT topology
mcfg = MeshConfig(data=2, model=4)
template_params = init_params(cfg, jax.random.PRNGKey(0))
template_opt = opt.init_opt_state(template_params)
step_got, state = CheckpointManager("{ckdir}").restore(
    {{"params": template_params, "opt": template_opt}})
assert step_got == 0
pspecs = param_pspecs(cfg, mcfg)
sh = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                  is_leaf=lambda x: isinstance(x, P))
params = reshard_restore(state["params"], sh)
opt_state = jax.tree.map(jnp.asarray, state["opt"])
tcfg = TrainConfig(lr=1e-3, warmup_steps=1, total_steps=10)
pipe = TokenPipeline(vocab=cfg.vocab, seq_len=16, global_batch=2)
step = jax.jit(make_train_step(cfg, tcfg))
with mesh:
    with axis_rules(("data",)):
        params, opt_state, m = step(params, opt_state, pipe.batch_at(1))
loss = float(m["loss"])
assert loss == loss and loss < 20
print("ELASTIC_OK", loss)
"""
    out = _run(restore_code, devices=8)
    assert "ELASTIC_OK" in out


# ---------------------------------------------------------------------------
# In-process multi-device tests: the CI ``test-multidevice`` lane runs this
# file under XLA_FLAGS=--xla_force_host_platform_device_count=8, where
# these execute directly (no subprocess); a 1-device session skips them.
# ---------------------------------------------------------------------------

import jax

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@needs_mesh
def test_axis_dot_cg_mesh_invariance(f64):
    """pcg inside an explicit shard_map body with the psum inner product
    (axis_dot) == the single-host solve: SAME iteration count, same x,
    under shard_map's default varying-axes check."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import kernel_mesh
    from repro.solvers.cg import axis_dot, pcg

    n = 512
    diag = jnp.linspace(1.0, 5.0, n, dtype=jnp.float64)
    b = jax.random.normal(jax.random.PRNGKey(0), (n, 2), dtype=jnp.float64)

    r_host = pcg(lambda v: diag[:, None] * v, b, ridge=0.1, tol=1e-10,
                 maxiter=200)

    mesh = kernel_mesh(8)

    def body(d_loc, b_loc):
        r = pcg(lambda v: d_loc[:, None] * v, b_loc, ridge=0.1, tol=1e-10,
                maxiter=200, dot=axis_dot("dev"))
        return r.x, r.iterations

    x_mesh, it_mesh = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("dev"), P("dev")),
        out_specs=(P("dev"), P())))(diag, b)
    assert int(it_mesh) == int(r_host.iterations)
    assert bool(r_host.converged)
    assert float(jnp.max(jnp.abs(x_mesh - r_host.x))) < 1e-10


@needs_mesh
def test_slq_logdet_shard_map_contract(f64):
    """slq_logdet under shard_map (local n, global n_total, psum
    all_reduce, per-device fold_in of the probe key) recovers the exact
    logdet of a diagonal operator with few distinct eigenvalues (the
    quadrature is exact once iters exceeds the spectrum size)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import kernel_mesh
    from repro.solvers import slq

    n = 512
    vals = jnp.asarray([1.0, 2.0, 4.0, 8.0], dtype=jnp.float64)
    diag = jnp.tile(vals, n // 4)
    exact = float(jnp.sum(jnp.log(diag)))
    mesh = kernel_mesh(8)

    def body(d_loc):
        key = jax.random.fold_in(jax.random.PRNGKey(3),
                                 jax.lax.axis_index("dev"))
        return slq.slq_logdet(
            lambda v: d_loc * v, d_loc.shape[0], probes=4, iters=8,
            key=key, dtype=jnp.float64,
            all_reduce=lambda s: jax.lax.psum(s, "dev"), n_total=n)

    ld = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P("dev"),),
                               out_specs=P()))(diag)
    assert abs(float(ld) - exact) < 1e-8 * abs(exact)


@needs_mesh
def test_sharded_operator_gspmd_solve(f64):
    """pcg + hmatrix.solve on subtree-sharded inputs (plain jit, GSPMD)
    match their single-host results — no hooks needed on this path."""
    import jax.numpy as jnp

    from repro.core import hmatrix
    from repro.core.hck import build_hck
    from repro.core.kernels_fn import BaseKernel
    from repro.launch.dist_hck import shard_by_subtree
    from repro.launch.mesh import kernel_mesh
    from repro.solvers.cg import pcg
    from repro.solvers.operators import HCKOp

    ker = BaseKernel("gaussian", sigma=2.0, jitter=1e-8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 4),
                          dtype=jnp.float64)
    f = build_hck(x, levels=3, rank=64, key=jax.random.PRNGKey(1),
                  kernel=ker)
    y = (jnp.sin(x[:, 0]) + 0.25 * jnp.cos(2.0 * x[:, 1]))[:, None]
    ys = y[f.tree.perm]
    mesh = kernel_mesh(8)

    op = HCKOp(f)
    r_host = pcg(op, ys, ridge=1e-2, tol=1e-8, maxiter=400)
    r_mesh = pcg(op.sharded(mesh), ys, ridge=1e-2, tol=1e-8, maxiter=400)
    assert bool(r_host.converged) and bool(r_mesh.converged)
    assert float(jnp.max(jnp.abs(r_mesh.x - r_host.x))) < 1e-6

    a_host = hmatrix.solve(f, ys, ridge=1e-2)
    a_mesh = hmatrix.solve(shard_by_subtree(f, mesh), ys, ridge=1e-2)
    assert float(jnp.max(jnp.abs(a_mesh - a_host))) < 1e-8


@needs_mesh
def test_mesh_predict_engine_matches_single_host(f64):
    """Device-routed serving == the single-host shape-bucketed engine,
    including an empty batch and a batch above max_bucket."""
    import jax.numpy as jnp

    from repro.core import hmatrix, oos
    from repro.core.hck import build_hck
    from repro.core.kernels_fn import BaseKernel
    from repro.launch.mesh import kernel_mesh
    from repro.serving.predict_service import PredictEngine

    ker = BaseKernel("gaussian", sigma=2.0, jitter=1e-8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 4),
                          dtype=jnp.float64)
    f = build_hck(x, levels=5, rank=16, key=jax.random.PRNGKey(1),
                  kernel=ker)
    y = (jnp.sin(x[:, 0]) + 0.25 * jnp.cos(2.0 * x[:, 1]))[:, None]
    alpha = hmatrix.solve(f, y[f.tree.perm], ridge=1e-2)
    plan = oos.prepare(f, alpha)
    eng = PredictEngine(f, plan, ker)
    mesh = kernel_mesh(8)
    m_eng = eng.on_mesh(mesh, min_bucket=16, max_bucket=128)

    assert m_eng.apply(jnp.zeros((0, 4), jnp.float64)).shape == (0, 1)
    for q in (1, 37, 300):          # 300 > max_bucket: micro-batches
        xq = jax.random.normal(jax.random.PRNGKey(q), (q, 4),
                               dtype=jnp.float64)
        z_host = eng.apply(xq)
        z_mesh = m_eng.apply(xq)
        assert z_mesh.shape == z_host.shape
        assert float(jnp.max(jnp.abs(z_mesh - z_host))) < 1e-10


