"""Sharding rules: resolution+fallback (abstract mesh), ZeRO-1, and a real
multi-device subprocess check that the sharded loss equals single-device."""
import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.models import cache_specs, init_cache, init_params, param_specs

MESH_SINGLE = shd.abstract_mesh((16, 16), ("data", "model"))
MESH_MULTI = shd.abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_resolve_divisibility_fallbacks():
    # 8 kv heads on a 16-way model axis -> replicated kv
    s = shd.resolve_spec(("embed", "kv_heads", "head_dim"), (8192, 8, 128),
                         MESH_SINGLE)
    assert s == P()
    # 64 q heads shard fine
    s = shd.resolve_spec(("embed", "heads", "head_dim"), (8192, 64, 128),
                         MESH_SINGLE)
    assert s == P(None, "model")
    # 60 experts don't divide 16 -> expert_mlp picks up the model axis
    s = shd.resolve_spec(("experts", "embed", "expert_mlp"), (60, 2048, 1408),
                         MESH_SINGLE)
    assert s == P(None, None, "model")
    # 256 experts divide -> expert axis sharded, expert_mlp left replicated
    s = shd.resolve_spec(("experts", "embed", "expert_mlp"), (256, 7168, 2048),
                         MESH_SINGLE)
    assert s == P("model")
    # batch over (pod,data) jointly on the multi-pod mesh
    s = shd.resolve_spec(("batch", "length"), (256, 4096), MESH_MULTI)
    assert s == P(("pod", "data"))
    # batch=1 (long_500k) falls back to replicated; cache_len absorbs axes
    s = shd.resolve_spec(("batch", "cache_len", "kv_heads", "head_dim"),
                         (1, 524288, 8, 128), MESH_SINGLE)
    assert s == P(None, ("data", "model"))


def test_no_axis_used_twice():
    for arch in configs.ARCHS:
        for shape in ("train_4k", "decode_32k"):
            cfg = configs.full_config(arch, shape)
            shapes = jax.eval_shape(
                lambda k: init_params(cfg, k), jax.random.PRNGKey(0)
            )
            specs = shd.tree_specs(param_specs(cfg), shapes, MESH_MULTI)
            for spec, leaf in zip(
                jax.tree_util.tree_leaves(
                    specs, is_leaf=lambda t: isinstance(t, P)
                ),
                jax.tree_util.tree_leaves(shapes),
            ):
                used = []
                for e in spec:
                    if e is None:
                        continue
                    used.extend((e,) if isinstance(e, str) else e)
                assert len(used) == len(set(used)), (arch, spec)
                # divisibility holds
                sizes = dict(zip(MESH_MULTI.axis_names, MESH_MULTI.axis_sizes))
                for e, dim in zip(spec, leaf.shape):
                    if e is None:
                        continue
                    axes = (e,) if isinstance(e, str) else e
                    prod = int(np.prod([sizes[a] for a in axes]))
                    assert dim % prod == 0, (arch, spec, leaf.shape)


def test_cache_specs_resolve_for_all_decode_cells():
    for arch, shape in configs.cells():
        if configs.SHAPES[shape].kind != "decode":
            continue
        cfg = configs.full_config(arch, shape)
        cache_shapes = jax.eval_shape(
            lambda: init_cache(cfg, configs.SHAPES[shape].batch, cfg.cdtype())
        )
        specs = shd.tree_specs(cache_specs(cfg), cache_shapes, MESH_SINGLE)
        assert jax.tree_util.tree_leaves(
            specs, is_leaf=lambda t: isinstance(t, P)
        )


def test_zero1_adds_data_axis():
    spec = shd.zero1_spec(P(None, "model"), (8192, 49152), MESH_SINGLE)
    assert spec == P("data", "model")
    # nothing divisible -> unchanged
    spec = shd.zero1_spec(P(), (7,), MESH_SINGLE)
    assert spec == P()
    # multi-pod uses both pod and data
    spec = shd.zero1_spec(P(None, "model"), (8192, 49152), MESH_MULTI)
    assert spec == P(("pod", "data"), "model")


def test_constrain_noop_without_context():
    import jax.numpy as jnp
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "batch", None)
    assert y is x


def test_sharded_loss_matches_single_device(subproc):
    out = subproc(
        """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.models import init_params, param_specs
from repro.training import init_train_state, make_train_step
from repro.optim import AdamWConfig

cfg = configs.smoke_config("internlm2-20b")
state = init_train_state(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)}
step = make_train_step(cfg, AdamWConfig())
_, m_single = jax.jit(step)(jax.tree_util.tree_map(jnp.copy, state), batch)

mesh = make_mesh((2, 2), ("data", "model"))
shapes = jax.eval_shape(lambda: state)
resolved = shd.tree_specs(param_specs(cfg), shapes["params"], mesh)
named = jax.tree_util.tree_map(
    lambda s: NamedSharding(mesh, s), resolved,
    is_leaf=lambda t: isinstance(t, P))
state_sh = {"params": named,
            "opt": {"mu": named, "nu": named,
                    "step": NamedSharding(mesh, P())}}
batch_sh = {"tokens": NamedSharding(mesh, P("data"))}
with mesh, shd.logical_axis_rules(None, mesh):
    f = jax.jit(step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None))
    new_state, m_sharded = f(state, batch)
a, b = float(m_single["loss"]), float(m_sharded["loss"])
assert abs(a - b) / abs(a) < 2e-4, (a, b)
# params actually sharded
leaf = jax.tree_util.tree_leaves(new_state["params"])[1]
assert len(leaf.sharding.device_set) >= 2
print("OK", a, b)
""",
        devices=4,
    )
    assert "OK" in out
