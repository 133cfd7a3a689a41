import numpy as np

from benchmark import matrices, reference


def test_fold_seed_takes_big_seeds():
    a = matrices.fold_seed(2 ** 40 + 3, 0, 1)
    assert 0 <= a < 2 ** 32
    assert a == matrices.fold_seed(2 ** 40 + 3, 0, 1)
    assert a != matrices.fold_seed(2 ** 40 + 3, 0, 2)


def test_host_and_device_hash_agree():
    import jax.numpy as jnp

    n, s = 96, 123456789
    i = np.arange(n)
    h = matrices.uniform_np(i[:, None], i[None, :], n, s)
    d = np.asarray(matrices.uniform_jnp(jnp.asarray(i)[:, None],
                                        jnp.asarray(i)[None, :], n,
                                        jnp.uint32(s)))
    np.testing.assert_array_equal(h, d)
    assert h.min() >= -1 and h.max() < 1


def test_dense_matrix_is_the_programs_seeded_family():
    # the copy gives the bits of the program's validation.seeded_entries
    import jax.numpy as jnp

    from conflux_tpu import validation

    n, s = 128, 77
    mine = np.asarray(matrices.dense_rows(0, n, n, jnp.uint32(s)))
    r = jnp.arange(n)
    theirs = np.asarray(validation.seeded_entries(r[:, None], r[None, :], n, s))
    np.testing.assert_array_equal(mine, theirs)


def test_factor_residual_agrees_with_an_independent_witness():
    # the benchmark's strips against the program's own (a second witness)
    import jax.numpy as jnp

    from conflux_tpu import validation
    from conflux_tpu.lu.single import lu_factor_blocked

    n, s = 256, 99
    A = matrices.dense_rows(0, n, n, jnp.uint32(s))
    LU, perm = lu_factor_blocked(A, v=64)
    mine = reference.lu_factor_residual(LU[None, None], perm, n, s, blk=64)
    theirs = validation.lu_residual_strips(LU, perm, s, block=64)
    assert mine < 1e-5
    assert abs(mine - theirs) <= 1e-3 * theirs + 1e-9
    # an altered factor is seen
    bad = reference.lu_factor_residual(LU.at[3, 5].add(1.0)[None, None],
                                       perm, n, s, blk=64)
    assert bad > 1e3 * mine
