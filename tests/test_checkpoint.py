"""Checkpoint durability + elastic re-shard restore of the object store."""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import ckpt
from repro.core.knobs import Knobs
from repro.core.store import store_from_knobs


def _store(seed: int):
    """A filled store with its embedding column in bf16 (npz has no bf16:
    the save path stores the bits)."""
    st = store_from_knobs(Knobs(server_capacity=16,
                                max_object_points_server=8), 8)
    rng = np.random.default_rng(seed)
    return st._replace(
        ids=jnp.arange(1, 17, dtype=jnp.int32),
        active=jnp.asarray(rng.random(16) < 0.5),
        embed=jnp.asarray(rng.normal(size=st.embed.shape), jnp.bfloat16),
        points=jnp.asarray(rng.normal(size=st.points.shape), jnp.float32),
        version=jnp.asarray(rng.integers(0, 9, 16), jnp.int32))


def _assert_equal(a_tree, b_tree):
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_roundtrip_bf16(tmp_path):
    store = _store(0)
    assert store.embed.dtype == jnp.bfloat16
    ckpt.save(tmp_path, 7, store)
    assert ckpt.latest_step(tmp_path) == 7
    _assert_equal(store, ckpt.restore(tmp_path, 7, store))


def test_retention_and_atomicity(tmp_path):
    store = _store(0)
    for s in (1, 2, 3, 4, 5):
        ckpt.save(tmp_path, s, store, keep=3)
    steps = sorted(int(p.name.split("_")[1])
                   for p in tmp_path.glob("step_*"))
    assert steps == [3, 4, 5]
    assert ckpt.latest_step(tmp_path) == 5


def test_elastic_reshard_restore(tmp_path):
    """Checkpoints are logical tensors: restore onto a different mesh shape
    (here: unsharded save -> 1x1 mesh with explicit shardings), the rescale
    path for node-count changes."""
    store = _store(1)
    ckpt.save(tmp_path, 1, store)

    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    shardings = jax.tree.map(
        lambda a: NamedSharding(mesh, P("data") if a.ndim else P()), store)
    back = ckpt.restore(tmp_path, 1, store, shardings=shardings)
    _assert_equal(store, back)
    for leaf, sh in zip(jax.tree.leaves(back), jax.tree.leaves(shardings)):
        assert leaf.sharding == sh
