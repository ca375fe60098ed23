"""Pallas kernels vs ref.py oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.md.system import DEFAULT_FF
from repro.kernels import ops, ref


# ---- pack -------------------------------------------------------------------

@pytest.mark.parametrize("p,m,f", [(64, 32, 4), (100, 60, 7), (16, 128, 3)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pack_matches_ref(p, m, f, dtype):
    rng = np.random.RandomState(p + m)
    src = rng.randn(p, f).astype(dtype)
    idx = rng.randint(-1, p, size=(m,)).astype(np.int32)
    out = np.asarray(ops.pack(jnp.asarray(src), jnp.asarray(idx)))
    np.testing.assert_allclose(out, ref.pack_ref(src, idx), rtol=1e-6)


# ---- nonbonded pair forces ----------------------------------------------------

@pytest.mark.parametrize("n,k", [(6, 8), (10, 16), (3, 24)])
def test_pair_forces_matches_ref(n, k):
    rng = np.random.RandomState(n * k)
    ff = DEFAULT_FF
    a = rng.uniform(0, 3.0, (n, k, 4)).astype(np.float32)
    b = rng.uniform(0, 3.0, (n, k, 4)).astype(np.float32)
    a[..., 3] = rng.uniform(-0.3, 0.3, (n, k))
    b[..., 3] = rng.uniform(-0.3, 0.3, (n, k))
    ta = rng.randint(-1, 2, (n, k)).astype(np.int32)
    tb = rng.randint(-1, 2, (n, k)).astype(np.int32)
    same = np.zeros((n,), np.int32)
    same[: n // 2] = 1
    b[same > 0] = a[same > 0]
    tb[same > 0] = ta[same > 0]

    fa, fb, pe = ops.pair_forces(*map(jnp.asarray, (a, b, ta, tb, same)), ff)
    ra, rb, rp = ref.pair_forces_ref(a, b, ta, tb, same, ff)
    scale = max(np.abs(ra).max(), 1.0)
    np.testing.assert_allclose(np.asarray(fa) / scale, ra / scale,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(fb) / scale, rb / scale,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(pe), rp,
                               rtol=2e-4, atol=2e-4)


def test_pair_forces_newton(  ):
    rng = np.random.RandomState(0)
    ff = DEFAULT_FF
    n, k = 4, 16
    a = rng.uniform(0, 2.5, (n, k, 4)).astype(np.float32)
    b = rng.uniform(0, 2.5, (n, k, 4)).astype(np.float32)
    ta = np.zeros((n, k), np.int32)
    tb = np.zeros((n, k), np.int32)
    same = np.zeros((n,), np.int32)
    fa, fb, _ = ops.pair_forces(*map(jnp.asarray, (a, b, ta, tb, same)), ff)
    total = np.asarray(fa).sum(axis=(1,)) + np.asarray(fb).sum(axis=(1,))
    # random placements include near-overlaps with r^-14 forces; Newton's
    # third law must hold relative to the force scale
    scale = max(np.abs(np.asarray(fa)).max(), 1.0)
    np.testing.assert_allclose(total / scale, 0.0, atol=1e-5)


# ---- flash attention -----------------------------------------------------------

@pytest.mark.parametrize("bh,l,s,g,hd", [
    (2, 64, 64, 1, 32), (1, 128, 128, 4, 16), (3, 32, 96, 2, 64)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [np.float32])
def test_flash_attention_matches_ref(bh, l, s, g, hd, causal, dtype):
    if causal and l != s:
        pytest.skip("causal requires L == S in this test")
    rng = np.random.RandomState(l + s)
    q = rng.randn(bh, l, g, hd).astype(dtype)
    k = rng.randn(bh, s, hd).astype(dtype)
    v = rng.randn(bh, s, hd).astype(dtype)
    out = ops.flash_attention(*map(jnp.asarray, (q, k, v)), causal=causal,
                              bq=32, bk=32)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), expect, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.RandomState(7)
    q = rng.randn(2, 64, 2, 32).astype(np.float32)
    k = rng.randn(2, 64, 32).astype(np.float32)
    v = rng.randn(2, 64, 32).astype(np.float32)
    out = ops.flash_attention(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), causal=True, bq=32, bk=32)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    assert np.abs(np.asarray(out, np.float64) - expect).max() < 0.06


# ---- distributed kernels (remote DMA) in subprocess -----------------------------

@pytest.mark.dist
def test_halo_put_and_fused_pulses(dist):
    out = dist("check_kernel_halo.py", devices=4)
    assert "check_kernel_halo OK" in out


# ---- no silent fallback ---------------------------------------------------------

def _refuse(*args, **kwargs):
    raise RuntimeError("kernel refused")


@pytest.mark.parametrize("backend,kernel", [("pallas", "pack"),
                                            ("signal", "put_signal")])
def test_halo_kernel_failure_raises(monkeypatch, backend, kernel):
    """A failing halo kernel propagates: no latch downgrades the plan to
    its jnp/ppermute oracle, on this call or any later one."""
    from repro.core.halo_plan import HaloPlan, HaloSpec
    from repro.kernels import halo_pack
    from repro.launch.mesh import make_mesh

    monkeypatch.setattr(halo_pack, kernel, _refuse)
    plan = HaloPlan.build(HaloSpec(("x",), (1,), backend=backend),
                          make_mesh((1,), ("x",)))
    x = jnp.arange(12.0, dtype=jnp.float32).reshape(4, 3)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="kernel refused"):
            plan.fwd(x)


def test_nb_kernel_failure_raises(monkeypatch):
    """The pallas force backend has no jnp fallback either."""
    from repro.core.md import MDEngine, make_grappa_like
    from repro.kernels import nonbonded
    from repro.launch.mesh import make_mesh

    monkeypatch.setattr(nonbonded, "pair_forces_accum", _refuse)
    eng = MDEngine(make_grappa_like(200, seed=5),
                   make_mesh((1, 1, 1), ("z", "y", "x")),
                   force_backend="pallas")
    assert eng.force_backend == "pallas"
    cf, ci = eng.init_state()
    for _ in range(2):
        with pytest.raises(RuntimeError, match="kernel refused"):
            eng.force_fn(cf, ci)


def test_interpret_mode_follows_the_platform():
    from repro.kernels import interpret_mode
    assert interpret_mode() is (jax.default_backend() == "cpu")
    assert interpret_mode(False) is False
    assert interpret_mode(True) is True


@pytest.mark.parametrize("axis", ["z", "x"])
def test_remote_kernels_mesh_path_in_tpu_interpreter(axis):
    """The remote-copy kernels' compiled-path protocol — peers addressed
    by mesh coordinates over all three MD axes, barrier handshake — run
    by Pallas' TPU interpreter (DMA/semaphore semantics with race
    detection), which the plain interpreter cannot emulate."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import PartitionSpec as P
    from repro.compat import shard_map_norep
    from repro.kernels import halo_pack
    from repro.launch.mesh import make_mesh

    tpu_interp = pltpu.InterpretParams(detect_races=True)
    rng = np.random.RandomState(3)
    n_local, F, M = 40, 5, 70
    x = jnp.asarray(rng.randn(n_local, 1, 1, F).astype(np.float32))
    idx = rng.randint(-1, n_local, M).astype(np.int32)
    maps = np.full((2, M), -1, np.int32)
    maps[0, :60] = rng.randint(0, n_local, 60)
    maps[1, :50] = rng.randint(0, n_local + M, 50)

    def body(lo):
        lo = lo.reshape(n_local, F)
        outs = [halo_pack.put_signal(lo, jnp.asarray(idx), axis=axis,
                                     ring=1, shift=s, chunk=32,
                                     interpret=tpu_interp)
                for s in (-1, 1)]
        fused = halo_pack.fused_pulses(lo, jnp.asarray(maps), axis=axis,
                                       ring=1, n_local=n_local, chunk=32,
                                       interpret=tpu_interp)
        return jnp.concatenate(outs + [fused.reshape(2 * M, F)])[None, None]

    spec = P("z", "y", "x")
    got = np.asarray(jax.jit(shard_map_norep(
        body, mesh=make_mesh((1, 1, 1), ("z", "y", "x")), in_specs=(spec,),
        out_specs=spec))(x)).reshape(4 * M, F)
    src = np.asarray(x).reshape(n_local, F)
    put = ref.pack_ref(src, idx)
    # ring of one: every put lands back here; pulse 2 forwards pulse 1
    p1 = ref.pack_ref(src, maps[0])
    p2 = np.concatenate([src, p1])[np.maximum(maps[1], 0)]
    p2[maps[1] < 0] = 0.0
    np.testing.assert_array_equal(got, np.concatenate([put, put, p1, p2]))
