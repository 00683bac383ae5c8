"""Chip smoke: drive the mapping and serving path once on a TPU and check
what comes out.

    python chip_smoke.py             # one chip: phase A (mapping), B (serving)
    python chip_smoke.py --chips 4   # four chips: the placed session tier

Phase A maps 720p keyframes through ``MappingServer.process_frame`` at the
``Knobs()`` defaults (capacity 4096, 2,000 points, 32 detections, depth
ratio 5, 512-d embeddings), checks the Mosaic-compiled ``lift_compact``
kernel against ``ref.lift_compact_ref`` on one keyframe, and answers a few
queries against a numpy flat sweep.  Phase B runs the serving loop (fleet
sync + query engine over a 10,000-object, 16,384-slot store at C=256) in
the sync and the overlapped schedule, asserts equal results, sent bytes
and final stores, and checks sampled query answers against a numpy flat
sweep.  ``--chips 4`` runs only the four-device phase: the same workload at
C=4096 with zone stores and session shards placed on a 2x2 mesh, compared
with the identical run with everything on device 0.

Timings printed here are smoke timings (wall clock, compile included where
marked), not benchmark numbers.  The last line of standard output is one
JSON object: ``{"ok": true, "device": {...}}``.  With no TPU the script
exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT))

SCORE_TOL = 1e-5          # f32 score agreement with the numpy oracle

# Phase B at the paper's widths: 512-d embeddings, 2,000 server and 200
# client points per object, 10,000 live objects in a 16,384-slot store
SERVING_CFG = dict(C=256, ticks=24, n_live=10_000, cap=16_384, E=512,
                   P=2000, Pc=200, nz=2, zcap=4096, churn=96, budget=32,
                   batch=8, max_batches=2, base_hz=1.0, burst_hz=8.0)
SHARDED_CLIENTS = 4096
SHARDED_TICKS = 10


def log(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, ok: bool, detail: str = "") -> None:
    log(f"check {name}: {'PASS' if ok else 'FAIL'}"
        + (f" ({detail})" if detail else ""))
    if not ok:
        raise AssertionError(f"check failed: {name} {detail}")


# ---------------------------------------------------------------------------
# numpy oracles
# ---------------------------------------------------------------------------
def oracle_topk(ids, active, embed, centroid, q, k, near=None):
    """Numpy flat sweep: eligible slots (active, inside the near radius),
    cosine score, descending, stable on ties; k + 1 ranks padded with
    (0, -inf) so a rank-k tie can be recognised."""
    import numpy as np
    ok = np.asarray(active, bool).copy()
    if near is not None:
        center, radius = near
        d = np.linalg.norm(centroid - np.asarray(center, np.float32), axis=1)
        ok &= d <= float(radius)
    sim = embed.astype(np.float64) @ np.asarray(q, np.float64)
    sim[~ok] = -np.inf
    order = np.argsort(-sim, kind="stable")[:k + 1]
    oids = np.where(np.isfinite(sim[order]), ids[order], 0)
    return oids, sim[order]


def topk_agrees(got_oids, got_scores, want_oids, want_scores,
                tol: float = SCORE_TOL) -> bool:
    """Scores agree rank by rank within ``tol``; ids agree except where the
    oracle's score at that rank is within ``tol`` of a neighbouring rank
    (a near tie either order may legitimately take)."""
    import numpy as np
    k = len(got_oids)
    gs = np.asarray(got_scores, np.float64)
    ws = np.asarray(want_scores, np.float64)
    fin = np.isfinite(ws[:k])
    if not np.array_equal(np.isfinite(gs), fin):
        return False
    if not np.allclose(gs[fin], ws[:k][fin], atol=tol, rtol=0):
        return False
    for i in range(k):
        if int(got_oids[i]) == int(want_oids[i]):
            continue
        near_tie = any(0 <= j < len(ws) and np.isfinite(ws[j])
                       and abs(ws[j] - ws[i]) <= tol for j in (i - 1, i + 1))
        if not near_tie:
            return False
    return True


# ---------------------------------------------------------------------------
# Phase A: mapping
# ---------------------------------------------------------------------------
def phase_mapping(*, knobs=None, embed_dim: int = 512, h: int = 720,
                  w: int = 1280, n_objects: int = 80, n_keyframes: int = 8,
                  seed: int = 0) -> dict:
    import numpy as np
    import jax
    import jax.numpy as jnp
    from functools import partial

    from repro.core import Knobs, MappingServer, Query, execute_query
    from repro.core import depth as depth_mod
    from repro.core.pipeline import LIFT_BUFFER
    from repro.data.scenes import make_scene, scene_stream
    from repro.kernels import lift_compact as lc
    from repro.kernels import ops, ref
    from repro.perception.embedder import OracleEmbedder

    kn = Knobs() if knobs is None else knobs
    emb = OracleEmbedder(embed_dim=embed_dim)
    srv = MappingServer(knobs=kn, embedder=emb)
    scene = make_scene(n_objects=n_objects, seed=seed)
    classes = {o.oid: o.class_id for o in scene.objects}
    frames = list(scene_stream(scene, n_frames=5 * n_keyframes,
                               keyframe_interval=5, h=h, w=w))
    key = jax.random.key(seed)
    walls = []
    for i, fr in enumerate(frames):
        t0 = time.perf_counter()
        srv.process_frame(fr, classes, jax.random.fold_in(key, i))
        walls.append(time.perf_counter() - t0)   # blocks on the new store
    st = jax.tree.map(np.asarray, srv.store)
    n_mapped = int(st.active.sum())
    log(f"phase A smoke timing: first keyframe {walls[0]:.3f} s (compile "
        f"included), steady mean {np.mean(walls[1:]):.4f} s over "
        f"{len(walls) - 1} keyframes; {n_mapped} objects mapped")
    check("mapping_store_populated",
          n_mapped > 0 and all(np.isfinite(np.asarray(a)).all()
                               for a in (st.embed, st.centroid, st.points)),
          f"{n_mapped} active")

    # the lift kernel vs the seed-composition oracle on one keyframe
    r = kn.depth_downsampling_ratio
    D = kn.max_detections_per_frame
    fr = frames[len(frames) // 2]
    inst_lo = fr.inst[::r, ::r]
    oids = np.asarray(fr.visible_ids, np.int32)[:D]
    masks = np.zeros((D,) + inst_lo.shape, bool)
    masks[:len(oids)] = inst_lo[None] == oids[:, None, None]
    args = (jnp.asarray(depth_mod.downsample_depth(fr.depth, r)),
            jnp.asarray(masks), jnp.asarray(fr.intrinsics),
            jnp.asarray(fr.pose, jnp.float32))
    kw = dict(stride=r, budget=kn.max_object_points_server,
              lift_cap=LIFT_BUFFER)
    t0 = time.perf_counter()
    got = jax.block_until_ready(
        jax.jit(partial(lc.lift_compact_pallas, **kw))(*args))
    log(f"phase A smoke timing: lift_compact_pallas first call "
        f"{time.perf_counter() - t0:.3f} s (compile included)")
    served = jax.block_until_ready(ops.lift_compact(*args, **kw))
    want = [np.asarray(a) for a in
            jax.jit(partial(ref.lift_compact_ref, **kw))(*args)]
    counts = (np.asarray(masks) & (np.asarray(args[0]) > lc.Z_EPS)[None]
              ).sum((1, 2))
    want[1] = np.where(counts > 0, want[1], 0)   # documented n = 0 case
    for label, out in (("kernel", got), ("ops.lift_compact", served)):
        diffs = {}
        ok = np.array_equal(np.asarray(out[1]), want[1])
        for name, g, wv in zip(("points", "centroid", "bbox_min",
                                "bbox_max"), out[:1] + out[2:],
                               want[:1] + want[2:]):
            g = np.asarray(g)
            diffs[name] = float(np.max(np.abs(g - wv)))
            ok &= np.allclose(g, wv, rtol=1e-5, atol=1e-4)
        check(f"lift_compact_{label}_vs_ref", ok and int(want[1].sum()) > 0,
              f"{int(want[1].sum())} points; max abs diff {diffs}")

    # a few open-vocabulary queries against a numpy flat sweep
    mapped = sorted(set(st.label[st.active].tolist()))[:4]
    ok_all = True
    for cid in mapped:
        q = emb.embed_text(int(cid))
        res = execute_query(srv.store, Query(embed=q, k=5))
        want_o, want_s = oracle_topk(st.ids, st.active, st.embed,
                                     st.centroid, np.asarray(q), 5)
        ok = topk_agrees(np.asarray(res.oids), np.asarray(res.scores),
                         want_o, want_s)
        top = int(np.asarray(res.slots)[0])
        ok &= top >= 0 and int(st.label[top]) == int(cid)
        ok_all &= ok
    check("mapping_queries_vs_numpy_oracle", ok_all and len(mapped) > 0,
          f"{len(mapped)} classes queried")
    return {"objects_mapped": n_mapped, "first_keyframe_s": walls[0],
            "steady_keyframe_s": float(np.mean(walls[1:]))}


# ---------------------------------------------------------------------------
# Phase B: serving
# ---------------------------------------------------------------------------
def _query_log(loop) -> list:
    """Submission-ordered (cid, Query) arrivals: request id i is the i-th."""
    return [a for tick in loop.loadgen.arrivals for a in tick]


def _host_cols(store):
    import numpy as np
    return (np.asarray(store.ids), np.asarray(store.active),
            np.asarray(store.embed), np.asarray(store.centroid))


def phase_serving(cfg: dict | None = None, *, donate: bool | None = None,
                  oracle_every: int = 4, oracle_per_tick: int = 4) -> dict:
    """Sync vs overlapped serving loop on one seeded workload."""
    import numpy as np
    import jax
    from benchmarks.serving_loop import build_serving_loop

    cfg = dict(SERVING_CFG if cfg is None else cfg)
    out = {}
    loops = {}
    n_checked = 0
    oracle_ok = True
    for overlap in (False, True):
        mode = "overlapped" if overlap else "sync"
        t0 = time.perf_counter()
        loop = build_serving_loop(cfg, overlap=overlap, donate=donate)
        build_s = time.perf_counter() - t0
        reqs = _query_log(loop)
        for t in range(cfg["ticks"]):
            pre = None
            if not overlap and t % oracle_every == 0:
                # the sync schedule serves tick t's queries against the
                # front published at the end of tick t - 1: snapshot it
                pre = _host_cols(loop.store.front)
            seen = set(loop.results)
            loop.tick()
            if pre is None:
                continue
            fresh = sorted(set(loop.results) - seen)[:oracle_per_tick]
            for rid in fresh:
                spec = reqs[rid][1]
                got = loop.results[rid]
                want_o, want_s = oracle_topk(
                    *pre, np.asarray(spec.embed), spec.k,
                    near=(np.asarray(spec.near[0]),
                          np.asarray(spec.near[1])))
                oracle_ok &= topk_agrees(got.oids, got.scores, want_o, want_s)
                n_checked += 1
        stats = loop.run(0)            # drain carried ticks and backlog
        ticks = loop.tick_ms
        log(f"phase B smoke timing ({mode}): build {build_s:.3f} s, first "
            f"tick {ticks[0] / 1e3:.3f} s (compile included), steady median "
            f"{np.median(ticks[1:]) / 1e3:.4f} s/tick over "
            f"{len(ticks) - 1} ticks; {stats['n_queries_served']} queries, "
            f"{stats['sent_bytes_total']} B sent")
        loops[mode] = loop
        out[f"{mode}_sent_bytes"] = stats["sent_bytes_total"]
        out[f"{mode}_queries"] = stats["n_queries_served"]
    a, b = loops["sync"], loops["overlapped"]
    check("serving_queries_vs_numpy_oracle", oracle_ok and n_checked > 0,
          f"{n_checked} sampled requests")
    same = set(a.results) == set(b.results) and all(
        np.array_equal(a.results[r].oids, b.results[r].oids)
        and np.array_equal(a.results[r].scores, b.results[r].scores)
        for r in a.results)
    check("serving_sync_vs_overlapped_query_results", same and
          len(a.results) > 0, f"{len(a.results)} requests")
    check("serving_sync_vs_overlapped_sent_bytes",
          a.sent_bytes == b.sent_bytes > 0, f"{a.sent_bytes} B")
    fa = jax.tree.map(np.asarray, a.store.front)
    fb = jax.tree.map(np.asarray, b.store.front)
    check("serving_sync_vs_overlapped_final_store",
          all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(fa),
                                                   jax.tree.leaves(fb))))
    return out


# ---------------------------------------------------------------------------
# Four-device phase: the placed session tier vs everything on device 0
# ---------------------------------------------------------------------------
@functools.cache
def _rows_checksum():
    """[C] order-sensitive checksum of every client's payload rows (a jit
    over the FleetBatch, run on the device that holds it)."""
    import jax
    import jax.numpy as jnp

    def rows(batch):
        acc = jnp.zeros((batch.oid.shape[0],), jnp.int32)
        for leaf in jax.tree.leaves(batch):
            if leaf.dtype == jnp.float32:
                bits = jax.lax.bitcast_convert_type(leaf, jnp.int32)
            elif leaf.dtype == jnp.float16:
                bits = jax.lax.bitcast_convert_type(
                    leaf, jnp.int16).astype(jnp.int32)
            else:
                bits = leaf.astype(jnp.int32)
            bits = bits.reshape(bits.shape[0], -1)
            wt = jnp.arange(1, bits.shape[1] + 1, dtype=jnp.int32) * 40503
            acc = acc * 31 + jnp.sum(bits * wt[None], axis=1)
        return acc

    return jax.jit(rows)


def _packet_digest(pkt):
    """Host digest of one zone's tick packets: the [C] wire accounting and
    the payload checksum of every client's rows."""
    import numpy as np
    rows = _rows_checksum()
    parts = getattr(pkt, "parts", None)
    if parts is None:
        payload = np.asarray(rows(pkt.batch))
    else:
        payload = np.zeros(pkt.counts.shape, np.int32)
        for s, part in enumerate(parts):
            if part is not None:
                payload[pkt.roster.members[s]] = np.asarray(rows(part.batch))
    return (np.asarray(pkt.nbytes).copy(), np.asarray(pkt.counts).copy(),
            np.asarray(pkt.seqs).copy(), payload)


def _run_sharded(cfg: dict, mesh, donate: bool | None) -> dict:
    import numpy as np
    import jax
    from benchmarks.serving_loop import build_serving_loop

    loop = build_serving_loop(cfg, overlap=True, donate=donate)
    if mesh is not None:
        loop.server.zoned.place_on(mesh)
        for tier in loop.server.sessions:
            tier.place_on(mesh)
    digests = []
    account = loop._account_packets

    def recording(packets, t):
        digests.extend((t, z) + _packet_digest(p) for z, p in packets)
        account(packets, t)

    loop._account_packets = recording
    t0 = time.perf_counter()
    stats = loop.run(cfg["ticks"])
    wall = time.perf_counter() - t0
    zones = [jax.tree.map(np.asarray, z) for z in loop.server.zoned.zones]
    placed = sorted({str(d) for z in loop.server.zoned.zones
                     for d in z.ids.devices()})
    shard_devs = sorted({str(d) for tier in loop.server.sessions
                         for p in tier.parts if p is not None
                         for d in p.sync.synced_version.devices()})
    out = {"digests": digests, "sent": stats["sent_bytes_total"],
           "results": {r: (v.oids, v.scores, v.slots)
                       for r, v in loop.results.items()},
           "zones": zones, "wall": wall, "ticks": list(loop.tick_ms),
           "zone_devices": placed, "shard_devices": shard_devs}
    del loop
    gc.collect()
    return out


def phase_sharded(cfg: dict | None = None, devices=None, *,
                  donate: bool | None = None) -> dict:
    """The serving workload at C=4096 with a 4-shard session tier per zone
    over a 2x2 zone grid: zone stores and session shards placed on a
    4-device mesh, against the same run with everything on device 0."""
    import numpy as np
    import jax
    from jax.sharding import Mesh

    if cfg is None:
        cfg = dict(SERVING_CFG, C=SHARDED_CLIENTS, ticks=SHARDED_TICKS,
                   base_hz=SERVING_CFG["base_hz"] * SERVING_CFG["C"]
                   / SHARDED_CLIENTS,
                   burst_hz=SERVING_CFG["burst_hz"] * SERVING_CFG["C"]
                   / SHARDED_CLIENTS)
    cfg = dict(cfg, shards=4, nz=2)
    devices = jax.devices()[:4] if devices is None else devices
    if len(devices) < 4:
        raise RuntimeError(f"the placed phase needs 4 devices, "
                           f"found {len(devices)}")
    mesh = Mesh(np.asarray(devices).reshape(2, 2), ("x", "y"))
    placed = _run_sharded(cfg, mesh, donate)
    single = _run_sharded(cfg, None, donate)
    for name, run in (("placed", placed), ("device 0", single)):
        log(f"sharded phase smoke timing ({name}): {run['wall']:.3f} s for "
            f"{len(run['ticks'])} ticks, first tick "
            f"{run['ticks'][0] / 1e3:.3f} s (compile included), steady "
            f"median {np.median(run['ticks'][1:]) / 1e3:.4f} s/tick; "
            f"zone stores on {run['zone_devices']}, session shards on "
            f"{run['shard_devices']}")
    check("sharded_placement_spans_4_devices",
          len(placed["zone_devices"]) == 4
          and len(placed["shard_devices"]) == 4
          and len(single["zone_devices"]) == 1,
          f"{placed['zone_devices']} / {placed['shard_devices']}")
    same_pk = len(placed["digests"]) == len(single["digests"]) > 0 and all(
        a[:2] == b[:2] and all(np.array_equal(x, y)
                               for x, y in zip(a[2:], b[2:]))
        for a, b in zip(placed["digests"], single["digests"]))
    check("sharded_wire_packets_equal_device0", same_pk,
          f"{len(placed['digests'])} zone packets")
    check("sharded_sent_bytes_equal_device0",
          placed["sent"] == single["sent"] > 0, f"{placed['sent']} B")
    ra, rb = placed["results"], single["results"]
    check("sharded_query_results_equal_device0",
          set(ra) == set(rb) and len(ra) > 0 and all(
              all(np.array_equal(x, y) for x, y in zip(ra[r], rb[r]))
              for r in ra), f"{len(ra)} requests")
    check("sharded_zone_stores_equal_device0", all(
        all(np.array_equal(x, y) for x, y in zip(jax.tree.leaves(za),
                                                 jax.tree.leaves(zb)))
        for za, zb in zip(placed["zones"], single["zones"])))
    return {"sent_bytes": placed["sent"], "requests": len(ra)}


# ---------------------------------------------------------------------------
def _peak_bytes(devices) -> str:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use"))
    return ", ".join(f"{d}: {p}" for d, p in zip(devices, peaks))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the placed four-device phase")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {devs[0].device_kind} x{len(devs)} "
        f"({devs[0].platform}); kernels interpret mode: {ops._interpret()}")
    assert not ops._interpret(), "kernels must compile, not interpret"

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(devices=devs[:4])
        used = devs[:4]
    else:
        phase_mapping()
        gc.collect()
        phase_serving()
        used = devs[:1]
    log(f"smoke wall: {time.perf_counter() - t0:.1f} s")
    log(f"peak_bytes_in_use: {_peak_bytes(used)}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
