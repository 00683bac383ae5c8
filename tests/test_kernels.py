"""Per-kernel allclose tests: Pallas (interpret) vs pure-jnp oracles,
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("n,e,k", [(100, 64, 5), (1024, 128, 8),
                                   (3000, 512, 10), (64, 32, 3)])
def test_query_topk(n, e, k):
    kq, ke, ka = jax.random.split(jax.random.key(n + e), 3)
    q = jax.random.normal(kq, (e,), jnp.float32)
    embeds = jax.random.normal(ke, (n, e), jnp.float32)
    active = jax.random.bernoulli(ka, 0.8, (n,))
    sv, si = ops.query_topk(q, embeds, active, k)
    rv, ri = ref.query_topk_ref(q, embeds, active, k)
    np.testing.assert_allclose(np.asarray(sv), np.asarray(rv), rtol=1e-5)
    # indices may differ on exact ties; scores must match at every rank
    assert np.all(np.asarray(active)[np.asarray(si)]), "picked inactive slot"


@pytest.mark.parametrize("n,k,block_n", [(3000, 5, 1024), (700, 16, 128)])
def test_query_topk_bias_ties_match_ref(n, k, block_n):
    """Exact score ties inside and across blocks resolve like the oracle's
    ``lax.top_k``: lower slot first, ids included."""
    from repro.kernels import query_topk as qt
    rng = np.random.default_rng(n)
    # small-integer rows and queries: every score is exact in f32 whatever
    # the summation order, so equal rows tie bit for bit in both paths
    base = rng.integers(-2, 3, size=(40, 64)).astype(np.float32)
    embeds = jnp.asarray(base[rng.integers(0, 40, size=n)])   # many dupes
    qs = jnp.asarray(rng.integers(-2, 3, size=(6, 64)).astype(np.float32))
    bias = np.zeros((6, n), np.float32)
    bias[:, rng.random(n) < 0.3] = qt.NEG                     # masked slots
    bias[1] = np.round(rng.normal(size=n), 1)                 # tied bonuses
    sv, si = qt.query_topk_bias_pallas(qs, embeds, jnp.asarray(bias), k,
                                       block_n=block_n, interpret=True)
    rv, ri = ref.query_topk_bias_ref(qs, embeds, jnp.asarray(bias), k)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(sv), np.asarray(rv), rtol=1e-6)


@pytest.mark.parametrize("d,h,w,stride,budget,cap,block_t", [
    (4, 24, 32, 1, 64, 4096, 256),
    (8, 48, 64, 5, 512, 4096, 512),
    (3, 20, 26, 2, 16, 32, 128),
    (6, 30, 40, 3, 100, 80, 512),     # budget > cap + non-divisible tiling
])
def test_lift_compact_kernel(d, h, w, stride, budget, cap, block_t):
    """Streaming Pallas lift_compact vs the seed-composition oracle: the
    one-hot MXU scatter + folded stats must reproduce points, counts,
    centroid, and bbox (empty objects excepted: the kernel reports the
    true n = 0 where the seed's downsample floor said 1)."""
    from repro.kernels import lift_compact as lc
    rng = np.random.default_rng(d * h + w)
    depth = jnp.asarray(np.where(rng.random((h, w)) > 0.25,
                                 rng.uniform(0.4, 6.0, (h, w)),
                                 0.0).astype(np.float32))
    masks = jnp.asarray(rng.random((d, h, w)) > 0.5)
    intr = jnp.asarray([0.9 * w, 0.9 * w, w / 2, h / 2], jnp.float32)
    q, _ = np.linalg.qr(rng.normal(size=(3, 3)))
    pose = np.eye(4, dtype=np.float32)
    pose[:3, :3] = q.astype(np.float32)
    pose[:3, 3] = rng.uniform(-1, 1, 3).astype(np.float32)
    got = lc.lift_compact_pallas(depth, masks, jnp.asarray(intr),
                                 jnp.asarray(pose), stride=stride,
                                 budget=budget, lift_cap=cap,
                                 block_t=block_t, interpret=True)
    want = [np.asarray(a) for a in ref.lift_compact_ref(
        depth, masks, intr, jnp.asarray(pose), stride=stride, budget=budget,
        lift_cap=cap)]
    counts = np.asarray((np.asarray(masks)
                         & (np.asarray(depth) > lc.Z_EPS)[None]).sum((1, 2)))
    want[1] = np.where(counts > 0, want[1], 0)
    for name, g, w_ in zip(["pts", "n", "cent", "mn", "mx"], got, want):
        np.testing.assert_allclose(np.asarray(g), w_, rtol=1e-4, atol=1e-4,
                                   err_msg=name)


@pytest.mark.parametrize("m,n,d", [(50, 70, 3), (256, 512, 3), (1000, 333, 3),
                                   (128, 128, 8)])
def test_nearest_dist(m, n, d):
    ka, kb, kv = jax.random.split(jax.random.key(m * n), 3)
    a = jax.random.normal(ka, (m, d), jnp.float32) * 2
    b = jax.random.normal(kb, (n, d), jnp.float32) * 2
    bv = jax.random.bernoulli(kv, 0.9, (n,))
    got = ops.nearest_dist(a, b, bv)
    want = ref.nearest_dist_ref(a, b, bv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)

