"""The pipelined GRASP exchange (overlap=True, default) vs the sequential
reference (overlap=False): loss within rtol 1e-6 at every step and params
within 1e-6 over >= 3 layers and >= 5 optimizer steps on the simulated
8-device mesh. The forward pass is pure data movement; the backward pass
of the fused gather reorders a few gradient sums, so the two agree to
float32 rounding and not bit for bit. Run standalone (own process — XLA's
host device count must be set before jax initialises); wired into
scripts/verify.sh.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from repro.dist import collectives as coll
from repro.nn import gnn as gnn_mod
from repro.configs import base as cfgs
from repro.core.reorder import reorder_ranks
from repro.graph import generate
from repro.graph.csr import apply_reorder
from repro.train import optimizer as opt_mod
from repro.launch.mesh import make_debug_mesh

P_DEV, N_LAYERS, N_STEPS = 8, 3, 5
mesh = make_debug_mesh(2, 4)   # P = 8
g = generate.rmat(9, 7, seed=1)
g = apply_reorder(g, reorder_ranks(g, "dbg"))
spec = coll.partition_spec_for(g.num_nodes, g.num_edges, P_DEV,
                               hot=128, pub_frac=1.0, edge_slack=3.0)
part = coll.grasp_partition(g, spec)
assert part["dropped"] == 0

cfg = cfgs.GNNConfig(name="pipe", kind="gin", n_layers=N_LAYERS, d_hidden=24)
d_feat, n_classes = 12, 5
rng = np.random.default_rng(0)
params0 = gnn_mod.init(jax.random.PRNGKey(0), cfg, d_feat=d_feat)
opt_init, opt_update = opt_mod.make(opt_mod.OptConfig(lr=1e-3))

x = rng.standard_normal((spec.num_nodes, d_feat)).astype(np.float32)
labels = rng.integers(0, n_classes, spec.num_nodes).astype(np.int32)
lab_own = np.zeros((P_DEV, spec.n_own), np.int32)
for p in range(P_DEV):
    hot_ids = np.arange(p * spec.hot_per_dev, (p + 1) * spec.hot_per_dev)
    cold_ids = spec.hot + np.arange(p * spec.cold_per_dev,
                                    (p + 1) * spec.cold_per_dev)
    lab_own[p] = labels[np.concatenate([hot_ids, cold_ids])]
batch = dict(x_hot=jnp.asarray(x[:spec.hot]),
             x_cold=jnp.asarray(x[spec.hot:].reshape(P_DEV, spec.cold_per_dev,
                                                     d_feat)),
             esrc=jnp.asarray(part["esrc"]), edst=jnp.asarray(part["edst"]),
             emask=jnp.asarray(part["emask"]), pub=jnp.asarray(part["pub"]),
             labels=jnp.asarray(lab_own))

traj, finals = {}, {}
for name, overlap in (("sequential", False), ("pipelined", True)):
    step, _ = coll.make_grasp_gin_step(spec, cfg, d_feat, n_classes, mesh,
                                       opt_update, overlap=overlap)
    p_, o_ = params0, opt_init(params0)
    losses = []
    with jax.set_mesh(mesh):
        jstep = jax.jit(step)
        for _ in range(N_STEPS):
            p_, o_, m = jstep(p_, o_, batch)
            losses.append(float(m["loss"]))
    traj[name] = losses
    finals[name] = p_
    print(f"{name:10s} losses: {[f'{v:.6f}' for v in losses]}")

LOSS_RTOL, PARAM_ATOL = 1e-6, 1e-6
np.testing.assert_allclose(traj["pipelined"], traj["sequential"],
                           rtol=LOSS_RTOL, atol=0,
                           err_msg="loss trajectories diverged")
leaves_s = jax.tree_util.tree_leaves(finals["sequential"])
leaves_p = jax.tree_util.tree_leaves(finals["pipelined"])
assert len(leaves_s) == len(leaves_p)
for i, (a, b) in enumerate(zip(leaves_s, leaves_p)):
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=0,
                               atol=PARAM_ATOL,
                               err_msg=f"param leaf {i} diverged")
print(f"pipelined GRASP step matches sequential (loss rtol {LOSS_RTOL}, "
      f"params atol {PARAM_ATOL}) over {N_LAYERS} layers x {N_STEPS} steps "
      f"on {P_DEV} devices")
