"""The jax shim: every API funnelled through repro.compat works on the
installed jax."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat


def test_axis_size_is_concrete_under_shard_map():
    mesh = Mesh(np.array(jax.devices()[:1]), ("a",))

    def local(x):
        k = compat.axis_size("a")
        assert isinstance(k, int), type(k)  # concrete: usable in range()
        return x * k

    out = compat.shard_map(
        local, mesh=mesh, in_specs=(P("a"),), out_specs=P("a")
    )(jnp.ones((4,)))
    np.testing.assert_array_equal(np.asarray(out), np.ones(4))


def test_pvary_is_identity_shaped():
    """pvary only retypes a value as device-varying: same shape, same
    values, inside shard_map where the cast is defined."""
    mesh = Mesh(np.array(jax.devices()[:1]), ("a",))
    x = jnp.arange(6.0).reshape(3, 2)
    out = compat.shard_map(
        lambda v: compat.pvary(v, ("a",)), mesh=mesh, in_specs=(P(),),
        out_specs=P("a"),
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


def test_element_block_spec_overlapping_windows():
    """Overlapping (stride < size) input blocks — the fused-kernel layout."""
    from jax.experimental import pallas as pl

    R, C, h, tile = 16, 8, 2, 4
    x = jnp.arange((R + 2 * h) * C, dtype=jnp.float32).reshape(R + 2 * h, C)

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...][h:h + tile]

    out = pl.pallas_call(
        kernel,
        grid=(R // tile,),
        in_specs=[compat.element_block_spec(
            (tile + 2 * h, C), lambda i: (i * tile, 0)
        )],
        out_specs=pl.BlockSpec((tile, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), jnp.float32),
        interpret=True,
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x[h:h + R]))


def test_use_compile_cache_follows_env(monkeypatch, tmp_path):
    """The env var wins and is left to JAX; otherwise one fixed in-repo
    path, the same on every call."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compat.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compat.use_compile_cache()
        assert got == str(compat.DEFAULT_COMPILE_CACHE) == compat.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == got
        assert compat.DEFAULT_COMPILE_CACHE.parent.joinpath("src").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_aot_roundtrip_and_unknown_kind():
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.arange(8.0)
    kind, blob = compat.aot_serialize(compat.aot_compile(f, x))
    assert kind == compat.AOT_KIND
    np.testing.assert_array_equal(
        np.asarray(compat.aot_deserialize(kind, blob)(x)), np.asarray(f(x))
    )
    with pytest.raises(ValueError, match="unknown persisted-executable"):
        compat.aot_deserialize("stablehlo", blob)
