"""The jax surface the repo calls (repro.common.jax_compat) and the
persistent compile-cache placement (repro.common.compile_cache)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.common import compile_cache
from repro.common import jax_compat as jc


def test_tpu_compiler_params_sets_dimension_semantics():
    params = jc.tpu_compiler_params(dimension_semantics=("parallel",))
    assert tuple(params.dimension_semantics) == ("parallel",)


def test_axis_type_has_auto():
    assert hasattr(jc.AxisType, "Auto")


def test_enable_x64_is_a_scope():
    with jc.enable_x64():
        assert jnp.zeros(1).dtype == jnp.float64
        assert jnp.arange(3).dtype == jnp.int64
    assert jnp.zeros(1).dtype == jnp.float32


def test_make_mesh_names_its_devices():
    mesh = jc.make_mesh((1,), ("data",), devices=jax.devices()[:1],
                        axis_types=(jc.AxisType.Auto,))
    assert list(mesh.devices.flat) == jax.devices()[:1]


# ---------------------------------------------------------------------------
# live smoke on the installed jax (single CPU device)
# ---------------------------------------------------------------------------


def test_make_mesh_and_ambient_mesh_roundtrip():
    mesh = jc.make_mesh((1,), ("data",), axis_types=(jc.AxisType.Auto,))
    assert mesh.axis_names == ("data",)
    with jc.set_mesh(mesh):
        ambient = jc.get_abstract_mesh()
        assert not ambient.empty
        assert tuple(ambient.axis_names) == ("data",)
    after = jc.get_abstract_mesh()
    assert after.empty


def test_shard_map_runs_on_single_device():
    mesh = jc.make_mesh((1,), ("data",), axis_types=(jc.AxisType.Auto,))
    fn = jc.shard_map(lambda x: x * 2, mesh=mesh,
                      in_specs=P("data"), out_specs=P("data"), check_vma=False)
    with jc.set_mesh(mesh):
        out = jax.jit(fn)(jnp.arange(4.0))
    assert out.tolist() == [0.0, 2.0, 4.0, 6.0]


def test_tree_helpers_roundtrip():
    tree = {"a": [1, 2], "b": 3}
    assert jc.tree_map(lambda x: x * 2, tree) == {"a": [2, 4], "b": 6}


# ---------------------------------------------------------------------------
# compile cache
# ---------------------------------------------------------------------------


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.CACHE_ENV, "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    # JAX reads the variable itself; nothing is set in code
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    checkout = compile_cache.DEFAULT_CACHE_DIR.parent
    assert (checkout / "src" / "repro" / "common"
            / "compile_cache.py").is_file()
    assert compile_cache.DEFAULT_CACHE_DIR.name == ".jax_cache"
