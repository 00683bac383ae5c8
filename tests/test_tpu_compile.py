"""Compile the served path's kernels and jitted steps for a TPU v5e chip
that is described, not attached (libtpu's compiler, no device).

Interpret mode cannot show what Mosaic refuses (primitives it does not
lower, unaligned layouts, more VMEM than a kernel may use) nor whether a
program fits the chip's 16 GB: these compiles can.  Each case lowers at the
paper's widths for one chip of a described ``v5e:2x2`` and checks
``memory_analysis()`` against 16 GB.  Nothing runs, so results and times
are out of scope here (``chip_smoke.py`` runs the same code on a chip).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load libtpu, and collection runs
in every test worker.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.core.knobs import Knobs

HBM_BYTES = 16 * 10 ** 9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, tree):
    """Abstract values of ``tree`` (arrays or shape structs) on one chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert total < HBM_BYTES, f"{total} B does not fit one chip"
    return total


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_lift_compact_kernel_compiles_at_720p(one_chip):
    """Knob defaults: 720p at depth ratio 5 -> 144 x 256, D=32, P=2000."""
    from repro.kernels import lift_compact as lc
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)
    f = jax.jit(partial(lc.lift_compact_pallas, stride=5, budget=2000,
                        lift_cap=4096, interpret=False))
    c = f.lower(S((144, 256)), S((32, 144, 256), jnp.bool_), S((4,)),
                S((4, 4))).compile()
    assert _has_kernel(c)
    _fits(c)


def test_query_topk_bias_kernel_compiles(one_chip):
    from repro.kernels import query_topk as qt
    S = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                           sharding=one_chip)
    f = jax.jit(partial(qt.query_topk_bias_pallas, k=5, interpret=False))
    c = f.lower(S((8, 512)), S((16384, 512)), S((8, 16384))).compile()
    assert _has_kernel(c)
    _fits(c)


def test_ingest_frame_pallas_branch_compiles(one_chip, monkeypatch):
    """The production one-dispatch keyframe ingest at the knob defaults,
    steered onto its TPU branch (ops.lift_compact keys off the backend)."""
    from repro.core.pipeline import MappingServer
    from repro.core.store import store_from_knobs
    from repro.perception.embedder import OracleEmbedder
    kn = Knobs()
    E = 512
    store = jax.eval_shape(lambda: store_from_knobs(kn, E))
    srv = MappingServer(knobs=kn, embedder=OracleEmbedder(embed_dim=E),
                        store=store)
    D = kn.max_detections_per_frame
    h, w = 720 // kn.depth_downsampling_ratio, 1280 // \
        kn.depth_downsampling_ratio
    args = (store,
            jax.ShapeDtypeStruct((h, w), jnp.float32),
            jax.ShapeDtypeStruct((D, h, w), jnp.bool_),
            jax.ShapeDtypeStruct((4,), jnp.float32),
            jax.ShapeDtypeStruct((4, 4), jnp.float32),
            jax.ShapeDtypeStruct((D,), jnp.int32),
            jax.ShapeDtypeStruct((D,), jnp.bool_),
            jax.eval_shape(lambda: jax.random.key(0)),
            jax.ShapeDtypeStruct((), jnp.int32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    try:
        c = srv._ingest.lower(*_spec(one_chip, args)).compile()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert _has_kernel(c), "ingest_frame did not take the Pallas branch"
    _fits(c)


def test_collect_fleet_compiles_at_c256(one_chip):
    """One venue zone's fleet collect: C=256 clients over a 4,096-slot
    shard at E=512, 2,000 server / 200 client points, 32 rows per client.
    It gathers whole rows of the shard's client-cloud column, so it takes
    no [capz, 2000, 3] server cloud and reads a few GB, where the
    per-(client, row) downsample it replaces read ~111 GB by the same
    count.  The mirror's scatter that writes the column compiles too."""
    from repro.core.store import init_store
    from repro.core.updates import class_budget_table
    from repro.server.session import _collect_fleet, _collect_fleet_donated
    from repro.server.zones import _zone_clouds, _zone_scatter
    kn = Knobs(max_object_points_client=200)
    C, N, E, P, Pc, B = 256, 4096, 512, 2000, 200, 128
    store = jax.eval_shape(lambda: init_store(N, E, P))
    budgets = jax.ShapeDtypeStruct(class_budget_table(kn).shape, jnp.int32)
    clouds = jax.eval_shape(
        lambda z, b: _zone_clouds(z, b, points_budget=Pc), store, budgets)
    assert clouds.points.shape == (N, Pc * 3)
    args = (store,
            jax.ShapeDtypeStruct((C, N), jnp.int32),
            jax.ShapeDtypeStruct((C, N), jnp.bool_),
            jax.ShapeDtypeStruct((N,), jnp.bool_),
            jax.ShapeDtypeStruct((C,), jnp.bool_),
            jax.ShapeDtypeStruct((C,), jnp.int32),
            jax.ShapeDtypeStruct((C, 3), jnp.float32))
    args = _spec(one_chip, args)
    for fn in (_collect_fleet, _collect_fleet_donated):
        c = fn.lower(*args, None, _spec(one_chip, budgets),
                     _spec(one_chip, clouds), budget=32, points_budget=Pc,
                     knobs=kn).compile()
        assert f"f32[{N},{P},3]" not in c.as_text()
        cost = c.cost_analysis()
        cost = cost[0] if isinstance(cost, list) else cost
        assert cost["bytes accessed"] < 10e9, cost["bytes accessed"]
        _fits(c)
    rows = jax.eval_shape(lambda: init_store(B, E, P))
    idx = jax.ShapeDtypeStruct((B,), jnp.int32)
    ok = jax.ShapeDtypeStruct((B,), jnp.bool_)
    s = _zone_scatter.lower(
        *_spec(one_chip, (store, clouds, rows, idx, ok, idx, ok, budgets)),
        points_budget=Pc).compile()
    _fits(s)


def test_room_keyframe_ingest_donated_compiles(one_chip, monkeypatch):
    """The shared room's served keyframe path at its widths (720p at
    ratio 5, D=32, 2,000 points, E=512, 4,096 slots): the donated fused
    ingest, Pallas branch, and the back buffer's row-copy catch-up."""
    from repro.core.pipeline import MappingServer
    from repro.core.store import store_from_knobs
    from repro.perception.embedder import OracleEmbedder
    from repro.serving.loop import CATCH_UP_RECORDS, _copy_rows_donated
    kn = Knobs()
    E = 512
    store = jax.eval_shape(lambda: store_from_knobs(kn, E))
    srv = MappingServer(knobs=kn, embedder=OracleEmbedder(embed_dim=E),
                        store=store, donate=True)
    D = kn.max_detections_per_frame
    h, w = 720 // kn.depth_downsampling_ratio, 1280 // \
        kn.depth_downsampling_ratio
    args = (store,
            jax.ShapeDtypeStruct((h, w), jnp.float32),
            jax.ShapeDtypeStruct((D, h, w), jnp.bool_),
            jax.ShapeDtypeStruct((4,), jnp.float32),
            jax.ShapeDtypeStruct((4, 4), jnp.float32),
            jax.ShapeDtypeStruct((D,), jnp.int32),
            jax.ShapeDtypeStruct((D,), jnp.bool_),
            jax.eval_shape(lambda: jax.random.key(0)),
            jax.ShapeDtypeStruct((), jnp.int32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    try:
        c = srv._ingest.lower(*_spec(one_chip, args)).compile()
        cu = _copy_rows_donated.lower(
            *_spec(one_chip, (store, store, tuple(
                jax.ShapeDtypeStruct((D,), jnp.int32)
                for _ in range(CATCH_UP_RECORDS))))
        ).compile()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert _has_kernel(c), "ingest_frame did not take the Pallas branch"
    # donated: the store's [cap, P, 3] cloud column is updated in place,
    # so the executable holds one store, not two
    m = c.memory_analysis()
    cloud = store.points.size * 4
    assert m.output_size_in_bytes - m.alias_size_in_bytes < cloud
    _fits(c)
    _fits(cu)
