"""Server-side per-frame semantic mapping pipeline (paper Fig. 2 + Sec. 3.1).

Three execution modes, matching the paper's Fig. 3 ablation bars:
  B        device-cloud baseline: frame-level sequential execution — each
           detected object runs the (compiled) per-object stages one after
           another, geometry uncapped into association.
  B+P      + object-level parallelism: the frame's detections are padded to
           a fixed object batch and every stage runs batched (one MXU
           dispatch instead of D sequential ones).
  B+P+SD   + object-level geometry downsampling: per-object clouds capped at
           max_object_points_server before association (= SemanticXR).

The production SemanticXR path is ONE jitted ``ingest_frame`` dispatch from
the padded instance masks all the way through embed -> fused
lift/compact/downsample/stats (kernels/lift_compact — no per-object argsort,
no [D, HW, 3] intermediate) -> associate -> prune: a single device round
trip per keyframe instead of the seed's four stage syncs.  Setting
``instrument=True`` opts into the staged execution with per-stage
``block_until_ready`` walls so Fig. 3's bar decomposition stays measurable;
B and B+P keep the seed stage implementations as ablation arms.

Perception models (detector stand-in = GT instance masks from the renderer;
embedder = perception/embedder.py) are identical across modes — observed
differences are system organization only (paper Sec. 4.2).  All stage
functions are jitted with shape-stable (padded) signatures so steady-state
latency is measured, not retracing.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import association as assoc
from repro.core import depth as depth_mod
from repro.core import geometry as geo
from repro.core.knobs import Knobs
from repro.core.store import ObjectStore, store_from_knobs
from repro.data.scenes import Frame
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs.trace import span as obs_span
from repro.perception.embedder import OracleEmbedder

LIFT_BUFFER = 4096   # uncapped per-object buffer (baseline mode)


class KeyframeInputs(NamedTuple):
    """One keyframe's detections as the fused ingest takes them (host
    arrays, padded to ``max_detections_per_frame``)."""
    depth: np.ndarray       # [H/r, W/r] f32 downsampled depth
    masks: np.ndarray       # [D, H/r, W/r] bool instance masks
    intrinsics: np.ndarray  # [4] f32, full resolution
    pose: np.ndarray        # [4, 4] f32 cam -> world
    cids: np.ndarray        # [D] int32 class ids
    valid: np.ndarray       # [D] bool


class KeyframeRecord(NamedTuple):
    """What one keyframe's ingest wrote, from the same dispatch.  Per
    detection [D]: the slot written (cap where none was), that row's id,
    version, observation count, label, point count, centroid and
    embedding after the write; the detection's best existing slot and its
    score, and whether it merged there.  ``n_pruned``: slots the transient
    prune turned off."""
    slot: jax.Array
    oid: jax.Array
    version: jax.Array
    obs: jax.Array
    label: jax.Array
    n_points: jax.Array
    centroid: jax.Array     # [D, 3]
    embed: jax.Array        # [D, E]
    target: jax.Array
    score: jax.Array
    matched: jax.Array
    n_pruned: jax.Array     # []


def keyframe_record(st: ObjectStore, res: assoc.Resolution,
                    n_pruned) -> KeyframeRecord:
    row = jnp.minimum(res.slot, st.ids.shape[0] - 1)
    return KeyframeRecord(
        slot=res.slot, oid=st.ids[row], version=st.version[row],
        obs=st.obs_count[row], label=st.label[row],
        n_points=st.n_points[row], centroid=st.centroid[row],
        embed=st.embed[row], target=res.target, score=res.score,
        matched=res.matched, n_pruned=n_pruned.astype(jnp.int32))


@dataclass
class StageTimes:
    detect_ms: float = 0.0
    embed_ms: float = 0.0
    lift_ms: float = 0.0
    associate_ms: float = 0.0
    ingest_ms: float = 0.0     # fused single-dispatch path (embed+lift+assoc)

    @property
    def total_ms(self):
        return (self.detect_ms + self.embed_ms + self.lift_ms +
                self.associate_ms + self.ingest_ms)

    def record(self, mode: str) -> None:
        """Feed the per-stage wall times into the process-wide metrics
        registry (no-op when none is installed)."""
        reg = obs_metrics.get_registry()
        if reg is None:
            return
        h = reg.histogram("mapping_stage_ms",
                          "per-keyframe mapping stage wall time (ms)")
        for stage in ("detect", "embed", "lift", "associate", "ingest"):
            v = getattr(self, f"{stage}_ms")
            if v > 0.0:
                h.observe(v, stage=stage, mode=mode)


@dataclass
class MappingServer:
    knobs: Knobs
    embedder: OracleEmbedder
    mode: str = "semanticxr"        # "baseline" | "parallel" | "semanticxr"
    instrument: bool = False        # semanticxr: staged timings vs one dispatch
    donate: bool | None = None      # donate the store to the fused ingest
    #                                 dispatch: the pre-frame store is dead
    #                                 once process_frame rebinds self.store,
    #                                 so XLA updates the [cap, ...] arrays in
    #                                 place instead of copying them per
    #                                 keyframe.  None = by backend
    #                                 (ops.donate_default: on for a TPU).
    #                                 Callers that hold a pre-frame store
    #                                 reference (snapshot readers, ablation
    #                                 oracles) pass False.
    store: ObjectStore = None
    frame_count: int = 0
    deferred: int = 0
    cluster_index: object = None    # repro.index.ClusterIndex | None

    def __post_init__(self):
        kn = self.knobs
        if self.donate is None:
            self.donate = ops.donate_default()
        if self.store is None:
            self.store = store_from_knobs(kn, self.embedder.embed_dim)
        r = kn.depth_downsampling_ratio
        budget = kn.max_object_points_server

        lift = partial(geo.lift_depth, stride=r, max_points=LIFT_BUFFER)
        # seed batched stages (B+P ablation arm): [D, ...] padded object batch
        self._lift_batch = jax.jit(jax.vmap(lift, in_axes=(None, 0, None,
                                                           None)))
        self._embed_batch = jax.jit(self.embedder.embed_observation)
        # sequential stages (baseline): one object at a time
        self._lift_one = jax.jit(lift)
        self._embed_one = jax.jit(
            lambda c, k: self.embedder.embed_observation(c[None], k)[0])

        # fused lift->compact->downsample->stats (SD instrumented arm): one
        # dispatch replaces lift_batch + down_batch + the per-detection
        # centroid pass inside association
        self._lift_fused = partial(ops.lift_compact, stride=r, budget=budget,
                                   lift_cap=LIFT_BUFFER)

        self._associate = jax.jit(lambda st, det, fr: assoc.associate(
            st, det, frame=fr, point_budget=budget))
        self._associate_cent = jax.jit(
            lambda st, det, cent, fr: assoc.associate(
                st, det, frame=fr, point_budget=budget, det_centroid=cent))
        self._prune = jax.jit(lambda st, fr: assoc.prune_transients(
            st, frame=fr, min_obs=kn.min_obs_before_sync))

        # the production path: ONE jitted dispatch per keyframe, returning
        # the pruned store and the record of what it wrote
        def ingest_frame(st, depth_lo, masks, intr, pose, cids, valid, key,
                         frame):
            embs = self.embedder.embed_observation(cids, key)
            pts, ns, cent, _, _ = ops.lift_compact(
                depth_lo, masks, intr, pose, stride=r, budget=budget,
                lift_cap=LIFT_BUFFER)
            det = assoc.Detections(embed=embs, label=cids, points=pts,
                                   n_points=ns, valid=valid)
            st, res = assoc.associate_rows(st, det, frame=frame,
                                           point_budget=budget,
                                           det_centroid=cent)
            out = assoc.prune_transients(st, frame=frame,
                                         min_obs=kn.min_obs_before_sync)
            n_pruned = (st.active & ~out.active).sum()
            return out, keyframe_record(out, res, n_pruned)

        self._ingest = jax.jit(ingest_frame,
                               donate_argnums=(0,) if self.donate else ())

    # ------------------------------------------------------------------
    def _detect(self, frame: Frame, classes: dict):
        """Detector stand-in: GT instance masks + mapping-policy filters.

        One vectorized bbox/area pass over the instance map's labelled
        pixels — no per-object ``np.nonzero`` loop and no [K, H, W]
        presence array (tens of MB a 720p keyframe, whose allocation made
        the detect's cost depend on the allocator's state) — with the
        deferral decision delegated to ``depth.mapping_gate``, the single
        home of the ``min_mapping_bbox_area`` logic (Sec. 3.3).
        Returns (class_ids [nd], masks_lo [nd, H/r, W/r] bool)."""
        kn = self.knobs
        r = kn.depth_downsampling_ratio
        inst_lo = frame.inst[::r, ::r] if r > 1 else frame.inst
        oids = np.asarray(frame.visible_ids, np.int32)
        cids = np.asarray([classes[int(o)] for o in oids], np.int32)
        if oids.size and kn.skip_mapping_set:
            m = ~np.isin(cids, np.asarray(kn.skip_mapping_set))
            oids, cids = oids[m], cids[m]
        if oids.size == 0:
            return cids[:0], np.zeros((0,) + inst_lo.shape, bool)

        # full-res bbox areas in one pass: each labelled pixel of a listed
        # object counts toward its [K, H] row and [K, W] column presence
        H, W = frame.inst.shape
        flat = frame.inst.ravel()
        pix = np.flatnonzero(flat)
        lut = np.full(max(int(flat.max()), int(oids.max())) + 1, -1,
                      np.int64)
        lut[oids] = np.arange(len(oids))
        k = lut[flat[pix]]
        pix, k = pix[k >= 0], k[k >= 0]
        K = len(oids)

        def extent(idx, L):                                    # [K, L] bool
            present = np.bincount(k * L + idx, minlength=K * L) \
                .reshape(K, L) > 0
            first = present.argmax(axis=1)
            last = L - 1 - present[:, ::-1].argmax(axis=1)
            return last - first + 1

        area = extent(pix // W, H) * extent(pix % W, W)
        keep = np.asarray(depth_mod.mapping_gate(
            area, kn, frame_pixels=frame.inst.size))
        self.deferred += int((~keep).sum())
        oids = oids[keep][: kn.max_detections_per_frame]
        cids = cids[keep][: kn.max_detections_per_frame]
        masks_lo = inst_lo[None, :, :] == oids[:, None, None]
        return cids, masks_lo

    def prepare(self, frame: Frame, classes: dict):
        """Host side of one keyframe: detect, then pad to the fused
        ingest's shapes.  Returns (``KeyframeInputs``, nd); the inputs are
        None when nothing is detected."""
        kn = self.knobs
        r = kn.depth_downsampling_ratio
        D = kn.max_detections_per_frame
        cids, masks_lo = self._detect(frame, classes)
        nd = len(cids)
        if nd == 0:
            return None, 0
        pad_m = np.zeros((D,) + masks_lo.shape[1:], bool)
        pad_m[:nd] = masks_lo
        return KeyframeInputs(
            depth=np.asarray(depth_mod.downsample_depth(frame.depth, r),
                             np.float32),
            masks=pad_m, intrinsics=np.asarray(frame.intrinsics, np.float32),
            pose=np.asarray(frame.pose, np.float32),
            cids=np.pad(cids, (0, D - nd)).astype(np.int32),
            valid=np.arange(D) < nd), nd

    def ingest_keyframe(self, store: ObjectStore, inputs: KeyframeInputs,
                        key: jax.Array, index: int):
        """Dispatch the fused ingest of one prepared keyframe (``index``:
        the frame counter the transient prune reads) onto ``store``,
        donated where ``donate`` is on.  Returns (store,
        ``KeyframeRecord``), both still on the device."""
        return self._ingest(store, *inputs, key, np.int32(index))

    # ------------------------------------------------------------------
    def process_frame(self, frame: Frame, classes: dict,
                      key: jax.Array) -> StageTimes:
        """Map one keyframe; returns per-stage wall times (Fig. 3)."""
        kn = self.knobs
        D = kn.max_detections_per_frame
        times = StageTimes()

        t0 = time.perf_counter()
        inputs, nd = self.prepare(frame, classes)
        times.detect_ms = (time.perf_counter() - t0) * 1e3
        if nd == 0:
            self.frame_count += 1
            times.record(self.mode)
            return times

        # --- production path: ONE dispatch from masks to pruned store
        if self.mode == "semanticxr" and not self.instrument:
            t0 = time.perf_counter()
            with obs_span("pipeline.ingest_frame", cat="ingest",
                          nd=nd) as sp:
                self.store, _ = self.ingest_keyframe(self.store, inputs, key,
                                                     self.frame_count)
                sp.fence(self.store.active)
            jax.block_until_ready(self.store.active)
            times.ingest_ms = (time.perf_counter() - t0) * 1e3
            self._maintain_index()
            self.frame_count += 1
            times.record(self.mode)
            return times

        # --- staged execution (B / B+P arms, and instrumented SD)
        cids_np = inputs.cids[:nd]
        masks_lo = inputs.masks[:nd]
        depth_lo = jnp.asarray(inputs.depth)
        intr = jnp.asarray(inputs.intrinsics)
        pose = jnp.asarray(inputs.pose)
        pad_c = jnp.asarray(inputs.cids)
        pad_m = inputs.masks
        valid = jnp.asarray(inputs.valid)
        # embedding (object-level parallelism: batch vs sequential)
        t0 = time.perf_counter()
        if self.mode == "baseline":
            embs = jnp.stack([self._embed_one(jnp.asarray(cids_np[i]),
                                              jax.random.fold_in(key, i))
                              for i in range(nd)])
        else:
            embs = self._embed_batch(pad_c, key)
        embs.block_until_ready()
        times.embed_ms = (time.perf_counter() - t0) * 1e3

        # lift to 3D
        cent = None
        t0 = time.perf_counter()
        if self.mode == "baseline":
            lifted = [self._lift_one(depth_lo, jnp.asarray(masks_lo[i]),
                                     intr, pose) for i in range(nd)]
            pts = jnp.stack([l[0] for l in lifted])
            ns = jnp.stack([l[1] for l in lifted])
        elif self.mode == "parallel":
            pts, ns, _ = self._lift_batch(depth_lo, jnp.asarray(pad_m), intr,
                                          pose)
        else:
            # fused kernel: lift + downsample + centroid/bbox in one sweep
            pts, ns, cent, _, _ = self._lift_fused(depth_lo,
                                                   jnp.asarray(pad_m), intr,
                                                   pose)
        pts.block_until_ready()
        times.lift_ms = (time.perf_counter() - t0) * 1e3

        # association + merge (store buffers hold the cap; baseline and
        # P modes carry the uncapped buffer into the merge path)
        t0 = time.perf_counter()
        if self.mode == "baseline":
            pad = D - nd
            pts = jnp.pad(pts, ((0, pad), (0, 0), (0, 0)))
            ns = jnp.pad(ns, (0, pad))
            embs = jnp.pad(embs, ((0, pad), (0, 0)))
        det = assoc.Detections(
            embed=embs,
            label=pad_c,
            points=pts,
            n_points=ns,
            valid=valid,
        )
        fr = jnp.asarray(self.frame_count)
        if cent is not None:
            self.store = self._associate_cent(self.store, det, cent, fr)
        else:
            self.store = self._associate(self.store, det, fr)
        self.store = self._prune(self.store, fr)
        jax.block_until_ready(self.store.active)
        times.associate_ms = (time.perf_counter() - t0) * 1e3

        self._maintain_index()
        self.frame_count += 1
        times.record(self.mode)
        return times

    # ------------------------------------------------------------------
    def enable_index(self, **kw) -> None:
        """Attach a cluster-summary index (repro.index) over the mapping
        store; every mapped keyframe then maintains it incrementally and
        ``CloudService.query_spec`` plans coarse-to-fine through it."""
        from repro.index import ClusterIndex
        self.cluster_index = ClusterIndex.for_target(self.store, **kw)

    def _maintain_index(self):
        if self.cluster_index is not None:
            self.cluster_index.refresh(self.store)
