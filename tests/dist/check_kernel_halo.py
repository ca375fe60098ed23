"""4-virtual-device check: the Pallas halo + NB kernels against jnp oracles.

Drives ``put_signal`` (both ring directions) and ``fused_pulses``
(independent + staged-dependent index maps, padding entries) inside a
shard_map and compares against ppermute oracles bit for bit; plus the NB
cluster-pair kernel with its scatter-accumulate epilogue
(``pair_forces_accum``) against a sequential numpy oracle, per device
inside the same shard_map.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python tests/dist/check_kernel_halo.py
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map_norep
from repro.core.md.system import DEFAULT_FF
from repro.kernels import halo_pack, nonbonded
from repro.launch.mesh import make_mesh

RING = 4


def run_sharded(mesh, body, *args, out_specs=P("z")):
    fn = shard_map_norep(body, mesh=mesh, in_specs=(P("z"),) * len(args),
                         out_specs=out_specs)
    return np.asarray(jax.jit(fn)(*args))


def main():
    assert len(jax.devices()) >= RING, "need 4 virtual devices"
    mesh = make_mesh((RING,), ("z",))
    rng = np.random.RandomState(0)
    n_local, F = 6, 3
    x = jnp.asarray(rng.randn(RING * n_local, F).astype(np.float32))

    # ---- put_signal, both directions ---------------------------------
    idx = jnp.asarray([0, 1, 4], dtype=jnp.int32)
    for shift, perm in ((-1, [(j, (j - 1) % RING) for j in range(RING)]),
                        (+1, [(j, (j + 1) % RING) for j in range(RING)])):
        got = run_sharded(
            mesh, functools.partial(halo_pack.put_signal, index_map=idx,
                                    axis="z", ring=RING, shift=shift), x)
        ref = run_sharded(
            mesh, lambda lo: lax.ppermute(jnp.take(lo, idx, axis=0), "z",
                                          perm), x)
        assert np.array_equal(got, ref), f"put_signal shift={shift}"
        print(f"put_signal shift={shift:+d}: bitwise == ppermute oracle")

    # ---- fused_pulses: pulse 1 independent, pulse 2 dependent+padded --
    maps = np.full((2, 4), -1, np.int32)
    maps[0] = [0, 1, 2, 3]            # independent rows
    maps[1, :3] = [4, n_local + 1, n_local + 3]   # own + prev-recv rows
    jmaps = jnp.asarray(maps)

    got = run_sharded(
        mesh, functools.partial(halo_pack.fused_pulses, index_maps=jmaps,
                                axis="z", ring=RING, n_local=n_local), x)

    def oracle(lo):
        perm = [(j, (j - 1) % RING) for j in range(RING)]
        outs, prev = [], jnp.zeros((4, F), lo.dtype)
        for p in range(2):
            mrow = jnp.asarray(maps[p])
            valid = mrow >= 0
            safe = jnp.maximum(mrow, 0)
            local = jnp.take(lo, jnp.clip(safe, 0, n_local - 1), axis=0)
            dep = jnp.take(prev, jnp.clip(safe - n_local, 0, 3), axis=0)
            rows = jnp.where((safe >= n_local)[:, None], dep, local)
            rows = jnp.where(valid[:, None], rows, 0.0)
            prev = lax.ppermute(rows, "z", perm)
            outs.append(prev)
        return jnp.stack(outs)

    ref = run_sharded(mesh, oracle, x)
    assert np.array_equal(got, ref), "fused_pulses vs staged oracle"
    # padding entries must land as zero rows
    assert np.all(got.reshape(RING, 2, 4, F)[:, 1, 3] == 0.0)
    print("fused_pulses: bitwise == staged-forwarding oracle "
          "(dependent entries + padding)")

    # ---- pack / unpack_add round trip --------------------------------
    rows = jnp.asarray(rng.randn(4, F).astype(np.float32))
    dst = jnp.asarray(rng.randn(n_local, F).astype(np.float32))
    pidx = jnp.asarray([5, 0, 3, 2], dtype=jnp.int32)
    packed = halo_pack.pack(dst, pidx)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(dst)[np.asarray(pidx)])
    added = halo_pack.unpack_add(dst, pidx, rows)
    ref_add = np.array(dst)
    ref_add[np.asarray(pidx)] += np.asarray(rows)
    np.testing.assert_allclose(np.asarray(added), ref_add, atol=0)
    print("pack/unpack_add: exact gather / scatter-add")

    # ---- NB pair kernel + scatter-accumulate epilogue vs oracle -------
    # each device runs the kernel on its own batch (sharded over z); the
    # pallas epilogue must match a strictly sequential accumulation
    n_pair, k, n_cells = 8, 8, 6
    a = rng.uniform(0, 2.5, (RING * n_pair, k, 4)).astype(np.float32)
    b = rng.uniform(0, 2.5, (RING * n_pair, k, 4)).astype(np.float32)
    ta = rng.randint(-1, 2, (RING * n_pair, k)).astype(np.int32)
    tb = rng.randint(-1, 2, (RING * n_pair, k)).astype(np.int32)
    same = np.zeros(RING * n_pair, np.int32)
    same[::4] = 1
    b[same > 0] = a[same > 0]
    tb[same > 0] = ta[same > 0]
    ca = rng.randint(0, n_cells, RING * n_pair).astype(np.int32)
    cb = rng.randint(0, n_cells, RING * n_pair).astype(np.int32)

    def nb_body(a, b, ta, tb, same, ca, cb):
        F, pe = nonbonded.pair_forces_accum(a, b, ta, tb, same, ca, cb,
                                            DEFAULT_FF, n_cells,
                                            epilogue="pallas")
        # the per-pair forces the epilogue consumed, from the same
        # program: interpret-mode rounding depends on the surrounding
        # XLA program, so the oracle must accumulate these exact values
        fa, fb, pe_ref = nonbonded.pair_forces(a, b, ta, tb, same,
                                               DEFAULT_FF)
        return F, pe, fa, fb, pe_ref

    fn = shard_map_norep(nb_body, mesh=mesh, in_specs=(P("z"),) * 7,
                         out_specs=(P("z"),) * 5)
    F_got, pe_got, fa, fb, pe_ref = jax.jit(fn)(
        *map(jnp.asarray, (a, b, ta, tb, same, ca, cb)))
    F_got = np.asarray(F_got).reshape(RING, n_cells, k, 3)
    fa, fb = np.asarray(fa), np.asarray(fb)
    F_ref = np.zeros((RING, n_cells, k, 3), np.float32)
    for i in range(RING * n_pair):
        F_ref[i // n_pair, ca[i]] += fa[i]
        F_ref[i // n_pair, cb[i]] += fb[i]
    assert np.array_equal(F_got, F_ref), "pair_forces_accum vs oracle"
    assert np.array_equal(np.asarray(pe_got).reshape(-1),
                          np.asarray(pe_ref)), "pair energies"
    print("pair_forces_accum: scatter epilogue bitwise == sequential "
          "oracle (4 device batches)")

    # ---- compiled-path protocol across devices (TPU interpreter) ------
    # mesh-coordinate peers over a (2,2,1) mesh + barrier handshake: the
    # plain interpreter cannot run this (one named axis only)
    from jax.experimental.pallas import tpu as pltpu
    mesh3 = make_mesh((2, 2, 1), ("z", "y", "x"))
    spec3 = P("z", "y", "x")
    x3 = jnp.asarray(rng.randn(2 * 30, 2 * 5, 1, F).astype(np.float32))
    idx3 = jnp.asarray(rng.randint(-1, 150, 200).astype(np.int32))
    tpu_interp = pltpu.InterpretParams(detect_races=True)
    for axis, ring in (("z", 2), ("y", 2), ("x", 1)):
        for shift in (-1, 1):
            perm = [(j, (j + shift) % ring) for j in range(ring)]

            def put3(lo, axis=axis, ring=ring, shift=shift):
                return halo_pack.put_signal(
                    lo.reshape(150, F), idx3, axis=axis, ring=ring,
                    shift=shift, chunk=64, interpret=tpu_interp
                ).reshape(1, 1, 1, 200, F)

            def oracle3(lo, axis=axis, perm=perm):
                rows = jnp.take(lo.reshape(150, F), jnp.maximum(idx3, 0),
                                axis=0)
                rows = jnp.where((idx3 >= 0)[:, None], rows, 0.0)
                return lax.ppermute(rows, axis, perm).reshape(1, 1, 1, 200,
                                                              F)

            got3, ref3 = (np.asarray(jax.jit(shard_map_norep(
                f, mesh=mesh3, in_specs=(spec3,), out_specs=spec3))(x3))
                for f in (put3, oracle3))
            assert np.array_equal(got3, ref3), ("tpu-interp put", axis,
                                                shift)
    print("put_signal on a (2,2,1) mesh in the TPU interpreter: bitwise "
          "== ppermute oracle on every axis and direction")

    print("check_kernel_halo OK")


if __name__ == "__main__":
    main()
