"""Sharding rules: every parameter/cache leaf of every arch gets a valid
spec on the production meshes (divisibility honored, no silent failures).
Uses AbstractMesh so no 512-device runtime is needed."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES, decode_context
from repro.launch.sharding import ROW_W, param_pspec
from repro.models import transformer as T
from repro.serve.kvcache import kv_pspec
from repro.runtime import abstract_mesh, use_mesh


def _mesh(multi=False):
    # device-free: the production meshes need 256/512 chips
    if multi:
        return abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    return abstract_mesh((16, 16), ("data", "model"))


def _key_struct():
    return jax.ShapeDtypeStruct((2,), jnp.uint32)  # threefry key data


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("multi", [False, True])
def test_param_specs_divide_everywhere(arch, multi):
    cfg = get_config(arch)
    mesh = _mesh(multi)
    params = jax.eval_shape(lambda k: T.model_init(k, cfg), _key_struct())

    def check(path, leaf):
        spec = param_pspec(path, leaf, mesh)
        assert len(spec) <= len(leaf.shape)
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            assert dim % n == 0, (path, leaf.shape, spec)
        return leaf

    jax.tree_util.tree_map_with_path(check, params)


def test_big_matmul_weights_are_actually_sharded():
    """FSDP+TP must shard every O(d^2) weight at least 16-ways."""
    cfg = get_config("llama3-8b")
    mesh = _mesh()
    params = jax.eval_shape(lambda k: T.model_init(k, cfg), _key_struct())

    def check(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name.startswith("W") and leaf.ndim >= 2 and leaf.size > 1e6:
            spec = param_pspec(path, leaf, mesh)
            ways = 1
            for ax in spec:
                if ax is None:
                    continue
                for a in (ax if isinstance(ax, tuple) else (ax,)):
                    ways *= mesh.shape[a]
            assert ways >= 16, (path, leaf.shape, spec)
        return leaf

    jax.tree_util.tree_map_with_path(check, params)


def test_row_col_split_is_consistent():
    mesh = _mesh()
    import jax.tree_util as jtu
    mk = lambda name: (jtu.DictKey(name),)
    wq = param_pspec(mk("Wq"), jax.ShapeDtypeStruct((4096, 4096), jnp.float32), mesh)
    wo = param_pspec(mk("Wo"), jax.ShapeDtypeStruct((4096, 4096), jnp.float32), mesh)
    assert wq == P("data", "model")      # column parallel
    assert wo == P("model", "data")      # row parallel


def test_moe_expert_sharding_modes():
    import jax.tree_util as jtu
    mesh = _mesh()
    path = (jtu.DictKey("moe"), jtu.DictKey("Wgate"))
    # 128 experts: EP over model
    s = param_pspec(path, jax.ShapeDtypeStruct((128, 2048, 768), jnp.float32), mesh)
    assert s == P("model", "data", None)
    # 8 experts: TP fallback inside experts
    s = param_pspec(path, jax.ShapeDtypeStruct((8, 4096, 14336), jnp.float32), mesh)
    assert s == P(None, "data", "model")
    path_d = (jtu.DictKey("moe"), jtu.DictKey("Wdown"))
    s = param_pspec(path_d, jax.ShapeDtypeStruct((8, 14336, 4096), jnp.float32), mesh)
    assert s == P(None, "model", "data")


def test_kv_policy_head_vs_length_sharding():
    mesh = _mesh()
    with use_mesh(mesh):
        # 16 kv heads on 16-way model: shard heads
        assert kv_pspec(128, 32896, 16)[2] == "model"
        # 8 kv heads: shard the length axis instead
        s = kv_pspec(128, 32896, 8)
        assert s[1] == "model" and s[2] is None
        # batch 1 (long_500k): no data sharding
        s = kv_pspec(1, 524416, 16)
        assert s[0] is None


def test_cache_shardings_cover_every_arch_decode():
    from repro.launch.sharding import cache_shardings
    mesh = jax.make_mesh((1, 1), ("data", "model"))  # real tiny mesh
    for arch in ARCH_IDS:
        cfg = get_config(arch).reduced()
        ctx, src = decode_context(cfg, 64)
        caches = jax.eval_shape(
            lambda: T.init_caches(cfg, 4, ctx, src_len=src))
        out = cache_shardings(caches, mesh)  # must not raise
        assert jax.tree.structure(out, is_leaf=lambda x: hasattr(x, "spec"))


# --- mesh serving (DESIGN.md §12) --------------------------------------------


def test_qtensor_pspecs_projection():
    """Dense-layout specs projected onto packed codes: the column entry
    always carries over; the contraction entry survives only when the
    PACKED row count divides the mesh axes and packing padded nothing."""
    from repro.core.qtensor import QTensor
    from repro.launch.sharding import qtensor_pspecs
    mesh = abstract_mesh((2, 4), ("data", "model"))

    q = QTensor.from_master(jnp.zeros((128, 64)), "ternary")  # codes (8, 64)
    cs, ss = qtensor_pspecs(P("data", "model"), q, mesh)
    assert cs == P("data", "model")      # 8 % 2 == 0, no pad: K entry kept
    assert ss is None                    # no per-channel scale

    q_pad = QTensor.from_master(jnp.zeros((120, 64)), "ternary")
    cs, _ = qtensor_pspecs(P("data", "model"), q_pad, mesh)
    assert cs == P(None, "model")        # pad rows: a shard boundary would
                                         # fall inside dequantize's pad-slice

    q_small = QTensor.from_master(jnp.zeros((48, 64)), "ternary")  # 3 rows
    cs, _ = qtensor_pspecs(P("data", "model"), q_small, mesh)
    assert cs == P(None, "model")        # 3 % 2: would split a pack word

    # leading stack axes carry over; per-output-channel scale follows the
    # column entry so dequantize's broadcast stays shard-local
    q3 = QTensor.from_master(jnp.zeros((4, 128, 64)), "ternary",
                             scale=jnp.ones((1, 1, 64)))
    cs, ss = qtensor_pspecs(P(None, "data", "model"), q3, mesh)
    assert cs == P(None, "data", "model")
    assert ss == P(None, None, "model")


def test_slot_axis_recovery():
    from repro.serve.kvcache import slot_axis
    assert slot_axis((2, 8, 48), (2, 1, 48)) == 1   # (L, B, H) rnn state
    assert slot_axis((8,), (1,)) == 0               # per-slot pos vector
    assert slot_axis((4, 16), (4, 16)) is None      # 1-slot pool
    with pytest.raises(ValueError, match="must be 1"):
        slot_axis((2, 8, 48), (2, 3, 48))


def test_serve_pool_shardings_structure():
    """Every pool leaf gets a NamedSharding keyed off its slot axis (the
    real data-axis placement is asserted on-device in test_mesh_engine)."""
    from repro.launch.sharding import serve_pool_shardings
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    pool = {"h": jnp.zeros((2, 8, 48)), "pos": jnp.zeros((8,), jnp.int32)}
    ref = {"h": jnp.zeros((2, 1, 48)), "pos": jnp.zeros((1,), jnp.int32)}
    out = serve_pool_shardings(pool, ref, mesh)
    assert set(out) == {"h", "pos"}
    assert all(hasattr(s, "spec") for s in jax.tree_util.tree_leaves(
        out, is_leaf=lambda x: hasattr(x, "spec")))
