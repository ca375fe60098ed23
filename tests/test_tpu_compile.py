"""The Pallas kernels compile for a TPU v5e at grappa-90k shapes.

The TPU compiler is installed on CPU-only hosts and compiles for a chip
that is described, not attached.  These tests compile every kernel of
the MD path with ``interpret=False`` at the shapes a grappa-90k run
feeds them (18x18x18 cells, K=36 slots, 4-float coordinate rows) and
assert the Mosaic custom call is in the compiled HLO: what the chip's
compiler refuses fails here, without chip time.  Nothing runs.

This is the only test file that describes the chip.  The topology is
built inside a module-scoped fixture (never at import), so only the
pytest worker that is handed this file loads the TPU library.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.compat import shard_map_norep
from repro.core.md.system import DEFAULT_FF
from repro.kernels import halo_pack, nonbonded

# grappa-90k on one chip: 18^3 cells of K=36 slots; the extended block
# is 19^3 cells, and each halo pulse packs rows of the row-major views
# (prod(shape[:d+1]), -1) the halo backends use: (rows, F, M) per dim
K = 36
N_EXT = 19 ** 3 + 1                  # extended cells + the sentinel row
PULSE_ROWS = {
    "z": (18, 18 * 18 * K * 4, 1),
    "y": (19 * 18, 18 * K * 4, 19),
    "x": (19 * 19 * 18, K * 4, 19 * 19),
    "x-index": (19 * 19 * 18, K * 2, 19 * 19),
}
N_PAIRS = 14 * 18 ** 3               # the full eighth-shell worklist


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=False)
def no_cache():
    """Compiles for a described chip are written to the persistent cache
    but can never be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("n", [1024, N_PAIRS])
def test_pair_forces_compiles(one_chip, no_cache, n):
    f = functools.partial(nonbonded.pair_forces, ff=DEFAULT_FF,
                          interpret=False)
    s = functools.partial(_sds, one_chip)
    _assert_kernel(_compile(f, s((n, K, 4), jnp.float32),
                            s((n, K, 4), jnp.float32), s((n, K), jnp.int32),
                            s((n, K), jnp.int32), s((n,), jnp.int32)))


@pytest.mark.parametrize("epilogue", ["xla", "pallas"])
def test_pair_forces_accum_compiles(one_chip, no_cache, epilogue):
    n = 1024

    def f(a, b, ta, tb, same, ca, cb, na, nb):
        return nonbonded.pair_forces_accum(
            a, b, ta, tb, same, ca, cb, DEFAULT_FF, N_EXT, cnt_a=na,
            cnt_b=nb, epilogue=epilogue, interpret=False)

    s = functools.partial(_sds, one_chip)
    ints = [s((n,), jnp.int32) for _ in range(5)]
    _assert_kernel(_compile(f, s((n, K, 4), jnp.float32),
                            s((n, K, 4), jnp.float32), s((n, K), jnp.int32),
                            s((n, K), jnp.int32), *ints))


@pytest.mark.parametrize("pulse", sorted(PULSE_ROWS))
def test_pack_compiles(one_chip, no_cache, pulse):
    rows, F, M = PULSE_ROWS[pulse]
    dtype = jnp.int32 if pulse.endswith("index") else jnp.float32
    f = functools.partial(halo_pack.pack, interpret=False)
    _assert_kernel(_compile(f, _sds(one_chip, (rows, F), dtype),
                            _sds(one_chip, (M,), jnp.int32)))


def test_pack_wire_compiles(one_chip, no_cache):
    rows, F, M = PULSE_ROWS["x"]
    f = functools.partial(halo_pack.pack, interpret=False,
                          wire_dtype="bfloat16")
    _assert_kernel(_compile(f, _sds(one_chip, (rows, F), jnp.float32),
                            _sds(one_chip, (M,), jnp.int32)))


@pytest.mark.parametrize("pulse", ["z", "y", "x"])
def test_unpack_add_compiles(one_chip, no_cache, pulse):
    rows, F, M = PULSE_ROWS[pulse]
    f = functools.partial(halo_pack.unpack_add, interpret=False)
    _assert_kernel(_compile(f, _sds(one_chip, (rows, F), jnp.float32),
                            _sds(one_chip, (M,), jnp.int32),
                            _sds(one_chip, (M, F), jnp.float32)))


@pytest.fixture(scope="module")
def mesh_2x2(topo):
    return Mesh(np.asarray(topo.devices[:4]).reshape(2, 2, 1),
                ("z", "y", "x"))


@pytest.mark.parametrize("shift", [-1, 1])
def test_put_signal_compiles_on_2x2(mesh_2x2, no_cache, shift):
    rows, F, M = PULSE_ROWS["x"]
    idx = jnp.arange(M, dtype=jnp.int32)

    def body(x):
        recv = halo_pack.put_signal(x.reshape(rows, F), idx, axis="z",
                                    ring=2, shift=shift, interpret=False)
        return recv.reshape(1, 1, 1, M, F)

    spec = P("z", "y", "x")
    fn = shard_map_norep(body, mesh=mesh_2x2, in_specs=(spec,),
                         out_specs=spec)
    x = jax.ShapeDtypeStruct((2 * 19 * 19, 2 * 18, 1, F), jnp.float32,
                             sharding=NamedSharding(mesh_2x2, spec))
    _assert_kernel(_compile(fn, x))


def test_fused_pulses_compiles_on_2x2(mesh_2x2, no_cache):
    rows, F, M = PULSE_ROWS["x"]
    maps = jnp.stack([jnp.arange(M, dtype=jnp.int32),
                      jnp.arange(M, dtype=jnp.int32) + rows])

    def body(x):
        out = halo_pack.fused_pulses(x.reshape(rows, F), maps, axis="z",
                                     ring=2, n_local=rows, interpret=False)
        return out.reshape(1, 1, 1, 2, M, F)

    spec = P("z", "y", "x")
    fn = shard_map_norep(body, mesh=mesh_2x2, in_specs=(spec,),
                         out_specs=spec)
    x = jax.ShapeDtypeStruct((2 * 19 * 19, 2 * 18, 1, F), jnp.float32,
                             sharding=NamedSharding(mesh_2x2, spec))
    _assert_kernel(_compile(fn, x))
