"""Gradient compression (int8+EF) and elastic re-meshing."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.compression import dequantize_int8, quantize_int8
from repro.runtime import StragglerDetector, plan_mesh
from repro.runtime.failure import HeartbeatMonitor


def test_quantize_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256,)) * 3.0, jnp.float32)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_int8_allreduce_matches_mean(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import int8_all_reduce_mean
mesh = jax.make_mesh((4,), ("data",))
x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 64)),
                jnp.float32)
f = shard_map(lambda v: int8_all_reduce_mean(v[0], "data"),
              mesh=mesh, in_specs=P("data"), out_specs=P(),
              check_vma=False)
got = np.asarray(f(x))
want = np.asarray(x.mean(0))
rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
assert rel < 0.02, rel
print("OK", rel)
""",
        devices=4,
    )
    assert "OK" in out


def test_error_feedback_convergence(subproc):
    """SGD on a quadratic with int8+EF gradient reduce converges to the
    same optimum as exact reduction (error feedback removes the bias)."""
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import tree_int8_all_reduce_mean
mesh = jax.make_mesh((4,), ("data",))
rng = np.random.default_rng(1)
target = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)

def run(compress):
    w = jnp.zeros((32,))
    err = {"w": jnp.zeros((32,))}
    def one(w, err, tgt):
        g = {"w": w - tgt.mean(0)}  # local grad per shard uses local target
        def inner(tgt_loc, w, e):
            gl = {"w": w - tgt_loc[0]}
            if compress:
                red, e2 = tree_int8_all_reduce_mean(gl, "data", {"w": e})
                return red["w"], e2["w"]
            return jax.lax.pmean(gl["w"], "data"), e
        f = shard_map(inner, mesh=mesh,
                      in_specs=(P("data"), P(), P()), out_specs=(P(), P()),
                      check_vma=False)
        gr, e2 = f(tgt, w, err["w"])
        return w - 0.3 * gr, {"w": e2}
    for _ in range(60):
        w, err = one(w, err, target)
    return np.asarray(w)

w_exact = run(False)
w_comp = run(True)
opt = np.asarray(target.mean(0))
assert np.abs(w_exact - opt).max() < 1e-3
assert np.abs(w_comp - opt).max() < 2e-2, np.abs(w_comp - opt).max()
print("OK")
""",
        devices=4,
    )
    assert "OK" in out


def test_plan_mesh():
    assert plan_mesh(256) in ((16, 16), (32, 8))
    d, m = plan_mesh(240)  # non-power-of-two device counts still factor
    assert d * m == 240
    d, m = plan_mesh(64, prefer_model=24)  # model must divide head count
    assert d * m == 64 and 24 % m == 0


def test_elastic_reshard_roundtrip(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.distributed import sharding as shd
from repro.models import param_specs
from repro.runtime.elastic import reshard_state, state_shardings
from repro.training import init_train_state

cfg = configs.smoke_config("codeqwen1.5-7b")
state = init_train_state(cfg, jax.random.PRNGKey(0))
host = jax.tree_util.tree_map(np.asarray, state["params"])
# "restore onto the smaller surviving mesh"
mesh2 = jax.make_mesh((2,), ("model",))
sh = state_shardings(param_specs(cfg), jax.eval_shape(lambda: state["params"]),
                     mesh2)
placed = reshard_state(host, sh)
for a, b in zip(jax.tree_util.tree_leaves(placed),
                jax.tree_util.tree_leaves(host)):
    np.testing.assert_array_equal(np.asarray(a), b)
print("OK")
""",
        devices=4,
    )
    assert "OK" in out


def test_heartbeat_monitor():
    hb = HeartbeatMonitor(timeout_s=5.0)
    hb.beat("a", 0.0)
    hb.beat("b", 0.0)
    hb.beat("a", 4.0)
    assert set(hb.alive(8.0)) == {"a"}
    assert set(hb.dead(8.0)) == {"b"}


def test_straggler_detector():
    d = StragglerDetector(min_samples=3)
    for t in range(6):
        for h in ("h0", "h1", "h2", "h3"):
            d.record(h, 1.0 if h != "h3" else 3.2)
    actions = {x.host: x.action for x in d.decisions()}
    assert actions["h3"] == "drop"
    assert actions["h0"] == "ok"
    assert d.to_drop() == ["h3"]
