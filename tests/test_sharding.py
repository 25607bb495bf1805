"""Logical-axis sharding rules, divisibility fixup, FSDP/ensure-model."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh


@pytest.fixture(scope="module")
def mesh1():
    return jax.make_mesh((1,), ("model",))


class FakeMesh:
    """Duck-typed mesh with arbitrary axis sizes (no devices needed)."""

    def __init__(self, **axes):
        self.shape = dict(axes)
        self.axis_names = tuple(axes)


class TestFixSpec:
    def test_drops_indivisible(self):
        m = FakeMesh(data=16, model=16)
        spec = sh.fix_spec(m, P(None, "model"), (10, 8))   # 8 % 16 != 0
        assert spec == P(None, None)

    def test_keeps_divisible(self):
        m = FakeMesh(data=16, model=16)
        assert sh.fix_spec(m, P("data", "model"), (32, 64)) == \
            P("data", "model")

    def test_tuple_axes(self):
        m = FakeMesh(pod=2, data=16)
        spec = sh.fix_spec(m, P(("pod", "data")), (64,))
        assert spec == P(("pod", "data"))
        spec2 = sh.fix_spec(m, P(("pod", "data")), (30,))
        assert spec2 == P(None)


class TestEnsureAxis:
    def test_rehomes_model(self):
        m = FakeMesh(data=16, model=16)
        # experts=60 dropped; model goes to the largest divisible dim
        spec = sh._ensure_axis(m, P(None, None, None), (60, 2048, 1408),
                               "model")
        assert spec == P(None, "model", None)

    def test_noop_when_present(self):
        m = FakeMesh(model=16)
        spec = sh._ensure_axis(m, P("model", None), (32, 64), "model")
        assert spec == P("model", None)


class TestFSDP:
    def test_adds_pod_data(self):
        m = FakeMesh(pod=2, data=16, model=16)
        spec = sh._add_fsdp(m, P(None, "model", None), (9, 64, 24576))
        assert spec == P(None, "model", ("pod", "data"))

    def test_skips_used_data(self):
        m = FakeMesh(data=16, model=16)
        spec = sh._add_fsdp(m, P("data", "model"), (32, 64))
        assert spec == P("data", "model")

    def test_fallback_data_only(self):
        m = FakeMesh(pod=2, data=16, model=16)
        # no dim divisible by 32, but dim0 divisible by 16
        spec = sh._add_fsdp(m, P(None, "model"), (48, 64))
        assert spec == P("data", "model")


class TestRules:
    def test_rules_filtered_by_mesh(self, mesh1):
        with sh.axis_rules(mesh1):
            # "data"/"pod" absent from this mesh -> batch becomes replicated
            assert sh.logical_to_spec(("batch", "embed")) == P(None, None)

    def test_shard_noop_without_mesh(self):
        x = jnp.ones((4, 4))
        assert sh.shard(x, "batch", "embed") is x

    def test_tree_shardings_divisibility(self, mesh1):
        # size-1 mesh axis divides everything; spec passes through
        tree = {"w": ("heads", None)}
        shapes = {"w": jax.ShapeDtypeStruct((7, 3), jnp.float32)}
        out = sh.tree_shardings(mesh1, tree, shapes)
        assert out["w"].spec in (P("model", None), P(None, None))
        # and a fake 16-way mesh drops the indivisible dim (unit logic)
        m = FakeMesh(model=16)
        assert sh.fix_spec(m, P("model", None), (7, 3)) == P(None, None)


class TestLaunchMeshes:
    """The launch meshes bind to ``with_sharding_constraint``: every axis
    is Auto (``jax.make_mesh`` alone builds Explicit axes, which refuse)."""

    @pytest.mark.parametrize("build", ["population", "elastic"])
    def test_axes_are_auto(self, build):
        from jax.sharding import AxisType, NamedSharding
        from repro.launch.mesh import make_mesh_for, make_population_mesh
        mesh = (make_population_mesh() if build == "population"
                else make_mesh_for(model_parallel=1))
        assert set(mesh.axis_types) == {AxisType.Auto}
        spec = NamedSharding(mesh, P(mesh.axis_names[0]))
        out = jax.jit(lambda x: jax.lax.with_sharding_constraint(x, spec))(
            jnp.arange(8.0))
        assert out.shape == (8,)

    def test_elastic_mesh_rejects_uneven_split(self):
        from repro.launch.mesh import make_mesh_for
        with pytest.raises(ValueError, match="model_parallel=3"):
            make_mesh_for(4, model_parallel=3)


class TestCompileCache:
    @pytest.fixture
    def restore_cache_dir(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_repo_dir_when_unset(self, monkeypatch, restore_cache_dir):
        import os
        from repro.launch import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cc.use_repo_compile_cache() == cc.REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == cc.REPO_CACHE_DIR
        assert os.path.basename(cc.REPO_CACHE_DIR) == ".jax_cache"
        assert os.path.isfile(os.path.join(
            os.path.dirname(cc.REPO_CACHE_DIR), "pyproject.toml"))

    @pytest.mark.parametrize("value", ["/elsewhere/cache", ""])
    def test_environment_wins(self, monkeypatch, restore_cache_dir, value):
        from repro.launch import compile_cache as cc
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", value)
        jax.config.update("jax_compilation_cache_dir", None)
        assert cc.use_repo_compile_cache() == value
        assert jax.config.jax_compilation_cache_dir is None
