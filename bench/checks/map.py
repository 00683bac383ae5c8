"""Plain reference for the ``map`` driver: a numpy replay of the shared room.

It imports nothing of the program; it uses ``bench/checks/serve.py``'s
packet, mirror and query replay.  Against what the timed loop produced it
checks:

- mapping, on the sampled keyframes: from the same depth and instance map
  it detects (the bbox-area gate, the detection cap), lifts each
  detection's masked pixels to world points in float64, downsamples them
  to the point budget and takes their centroid; it embeds each detection
  as the stand-in embedder does (class basis plus the keyframe's noise);
  then it associates them with the program's own pre-keyframe store
  columns, scores in float64.  Those columns are first held to the
  records replayed up to the keyframe (the same live objects, each row's
  id, version, count, label, point count, last sighting, centroid and
  embedding bit for bit), and each scored row's stored cloud to its
  centroid, so a back buffer caught up wrongly shows there.  Each
  detection's decision (merge or insert) and target slot must match
  wherever the reference's best score clears the 0.6 threshold, its
  runner-up and any competing claimant by more than the score limit;
  the merged rows' points, centroid, embedding, count, id, version and
  label, the insert slots and the prune's count must match;
- the store over time, in object-id space: every keyframe's record
  (the rows it wrote) is replayed in keyframe order, each merge must
  advance its object's version by one, each insert must take the next
  id at version 1, and the reference runs the transient prune itself;
- from that store, ``serve.Reference``'s replay: every packet of the
  sampled viewers (rows owed, versions, priority order, wire bytes), the
  delivery guarantee, each sampled query's top-k against a float64 flat
  sweep, and the zone mirror after the run.

With ``control`` the reference's own lower-precision work stands in for
the program's: association scores and merged embeddings from
bfloat16-rounded embeddings, and lifted clouds and centroids from
bfloat16-rounded depth.
"""
from __future__ import annotations

import numpy as np

from bench.checks import serve

SCORE_LIMIT = 3e-5
# limits of the compared numbers: see PERF.md for the readings they were
# set from
LIMITS = {
    # the association score and merged embedding: f32 over 512 terms is
    # ~1e-7 off float64; one bf16 pass is ~1e-3 off
    "assoc_score_gap": SCORE_LIMIT,
    # lifted, merged and stored points: f32 unprojection within 8 m is
    # ~1e-6 m off; bf16 depth is ~1e-2 m off
    "lift_gap_m": 2e-5,
    "centroid_gap_m": 2e-5,
    "query_score_gap": serve.LIMITS["query_score_gap"],
    "assoc_faults": 0, "packet_faults": 0, "mirror_faults": 0,
    "query_faults": 0,
    # guarantees of the configuration
    "unmapped_keyframes": 0, "unanswered_queries": 0, "undelivered_pairs": 0,
}
# the association's semantics (paper Sec. 2.3.1), held fixed here
MATCH_THRESHOLD = 0.6       # combined score at which a detection merges
SPATIAL_SIGMA_M = 0.75      # spatial term exp(-d^2 / (2 sigma^2))
EMA = 0.25                  # weight of the new view in a merged embedding
PRUNE_MAX_AGE = 30          # keyframes an unconfirmed object may go unseen
LIFT_CAP = 4096             # valid pixels lifted per detection at most
REF_SENSOR_PIXELS = 720 * 1280   # the bbox gate's units (full 720p sensor)
Z_EPS = 1e-4                     # a pixel with depth below this is no hit


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def class_basis(cfg: dict, seed: int) -> np.ndarray:
    """[classes, E] float64 unit class embeddings of the stand-in embedder
    drawn from ``seed``."""
    b = np.random.default_rng(seed).normal(
        size=(cfg["embedder"]["classes"], cfg["embed_dim"]))
    return b / np.linalg.norm(b, axis=1, keepdims=True)


def _unit(x):
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# one keyframe, from the frame
# ---------------------------------------------------------------------------
def detect(frame, classes: dict, cfg: dict):
    """(class ids [nd], masks [nd, H/r, W/r]) of the detections mapped now:
    visible objects whose full-resolution bbox area, in 720p units, reaches
    the gate, in the frame's order, at most ``max_detections``."""
    r = cfg["depth_ratio"]
    inst = frame.inst
    keep = []
    for o in np.asarray(frame.visible_ids):
        ys, xs = np.nonzero(inst == o)
        area = (ys.max() - ys.min() + 1) * (xs.max() - xs.min() + 1)
        if r <= 1 or area * REF_SENSOR_PIXELS / inst.size \
                >= cfg["min_bbox_px"]:
            keep.append(int(o))
    keep = keep[:cfg["max_detections"]]
    lo = inst[::r, ::r]
    return (np.asarray([classes[o] for o in keep], np.int64),
            np.stack([lo == o for o in keep]) if keep
            else np.zeros((0,) + lo.shape, bool))


def lift(frame, mask_lo: np.ndarray, cfg: dict, rnd=None) -> np.ndarray:
    """[n, 3] float64 world points of one detection: its valid pixels in
    row-major order, the first ``LIFT_CAP`` kept, stride-downsampled to
    the point budget (output i takes rank floor(i * n / budget)).
    ``rnd`` rounds the depth each point is lifted from."""
    r, budget = cfg["depth_ratio"], cfg["server_points"]
    depth = frame.depth[::r, ::r].astype(np.float64)
    ok = mask_lo & (depth > Z_EPS)
    pix = np.flatnonzero(ok)[:LIFT_CAP]
    n = len(pix)
    if n > budget:
        pix = pix[(np.arange(budget) * n) // budget]
    W = depth.shape[1]
    z = depth.reshape(-1)[pix]
    if rnd is not None:
        z = rnd(z)
    fx, fy, cx, cy = np.asarray(frame.intrinsics, np.float64)
    x = (((pix % W) + 0.5) * r - cx) / fx * z
    y = (((pix // W) + 0.5) * r - cy) / fy * z
    pose = np.asarray(frame.pose, np.float32).astype(np.float64)
    return np.stack([x, y, z], 1) @ pose[:3, :3].T + pose[:3, 3]


def merge(pts_a: np.ndarray, pts_b: np.ndarray, budget: int):
    """The association merge: row i of the merged cloud is a[i] for
    i < len(a) else b[i - len(a)], stride-downsampled to ``budget``; an
    empty result keeps one (zero) point."""
    both = np.concatenate([pts_a, pts_b]) if len(pts_a) + len(pts_b) \
        else np.zeros((1, 3))
    n = len(both)
    idx = (np.arange(budget) * n) // budget if n > budget else np.arange(n)
    return both[idx]


def scores(e: np.ndarray, cent: np.ndarray, pre: dict, valid) -> np.ndarray:
    """[D, cap] combined scores: half spatial kernel, half cosine."""
    d2 = ((cent[:, None, :] - pre["centroid"].astype(np.float64)[None])
          ** 2).sum(-1)
    s = 0.5 * np.exp(-d2 / (2 * SPATIAL_SIGMA_M ** 2)) \
        + 0.5 * (e @ pre["embed"].astype(np.float64).T)
    s[:, ~pre["active"]] = -np.inf
    s[~valid] = -np.inf
    return s


def check_keyframe(index: int, frame, classes: dict, noise: np.ndarray,
                   basis: np.ndarray, cfg: dict, pre: dict, post_points,
                   rec, control: bool = False) -> dict:
    """Compare one sampled keyframe's ingest with the reference.  ``pre``:
    the program's store columns before it, and ``points``: the clouds of
    the rows it targeted, in detection order; ``post_points``: the written
    rows' clouds after it; ``rec``: its record (host); ``noise``: the
    embedder's [D, E] normal draws for it.  Returns the gaps, the faults
    and whether every decision was clear; the stored clouds' gap to their
    centroids is read whatever the decisions."""
    E, D = cfg["embed_dim"], cfg["max_detections"]
    budget = cfg["server_points"]
    cap = len(pre["active"])
    cids, masks = detect(frame, classes, cfg)
    nd = len(cids)
    out = {"score_gap": 0.0, "lift_gap": 0.0, "cent_gap": 0.0,
           "faults": {}, "clear": True}

    def fault(what, n=1):
        if n:
            out["faults"][what] = out["faults"].get(what, 0) + int(n)

    valid = np.arange(D) < nd
    pts = [lift(frame, m, cfg) for m in masks]
    cent = np.zeros((D, 3))
    for d, p in enumerate(pts):
        cent[d] = p.mean(0) if len(p) else 0.0
    e = _unit(basis[np.pad(cids, (0, D - nd))]
              + noise * (cfg["embedder"]["noise"] / np.sqrt(E)))
    ref = scores(e, cent, pre, valid)
    prog_t = np.minimum(rec.target.astype(np.int64), cap - 1)
    got = np.asarray(rec.score, np.float64)
    if control:     # bfloat16 embeddings in the program's place
        bpre = dict(pre, embed=_bf16(pre["embed"]))
        got = scores(_bf16(e), cent, bpre, valid)[np.arange(D), prog_t]
    j = np.argmax(ref, axis=1)
    best = ref[np.arange(D), j]
    second = np.sort(ref, axis=1)[:, -2] if cap > 1 else np.full(D, -np.inf)
    fin = valid & np.isfinite(best)
    # each scored row's stored cloud has its stored centroid for mean
    for d in np.nonzero(fin)[0]:
        k = int(prog_t[d])
        na = int(pre["n_points"][k])
        if pre["active"][k] and na:
            out["lift_gap"] = max(out["lift_gap"], float(np.max(np.abs(
                pre["points"][d][:na].astype(np.float64).mean(0)
                - pre["centroid"][k].astype(np.float64)))))
    if fin.any():
        out["score_gap"] = float(np.max(np.abs(
            got[fin] - ref[np.arange(D), prog_t][fin])))
    wants = fin & (best >= MATCH_THRESHOLD)
    winner = {}
    for d in np.nonzero(wants)[0]:
        if int(j[d]) not in winner or best[d] > best[winner[int(j[d])]]:
            winner[int(j[d])] = d
    matched = np.array([bool(wants[d]) and winner[int(j[d])] == d
                        for d in range(D)], bool)
    # a decision is clear when no score lies within the limit of what
    # decided it: the threshold, the runner-up, a competing claimant
    with np.errstate(invalid="ignore"):
        clear = ~fin | ((np.abs(best - MATCH_THRESHOLD) > SCORE_LIMIT)
                        & ~(best - second <= SCORE_LIMIT))
    for d in np.nonzero(wants)[0]:
        rival = wants & (j == j[d]) & (np.arange(D) != d)
        if (np.abs(best[rival] - best[d]) <= SCORE_LIMIT).any():
            clear[d] = False
    if not clear.all():
        out["clear"] = False
        return out
    fault("decision", (matched != rec.matched)[valid].sum())
    fault("target", (fin & (j != rec.target)).sum())

    # inserts take free slots in detection order; ids follow next_id
    free = np.flatnonzero(~(pre["active"] | pre["deleted"]))
    do_ins = valid & ~matched
    rank = np.cumsum(do_ins) - 1
    slot = np.full(D, cap)
    slot[matched] = j[matched]
    ok_ins = do_ins & (rank < len(free))
    slot[ok_ins] = free[rank[ok_ins]]
    fault("slot", (slot != rec.slot).sum())
    if out["faults"]:
        return out

    last_seen = pre["last_seen"].astype(np.int64)
    obs = pre["obs_count"].astype(np.int64)
    for d in np.nonzero(slot < cap)[0]:
        k = int(slot[d])
        old = np.zeros((0, 3))
        if matched[d]:
            old = pre["points"][d][:int(pre["n_points"][k])].astype(
                np.float64)
            cloud = merge(old, pts[d], budget)
            pe = pre["embed"][k].astype(np.float64)
            m_ref = _unit((1 - EMA) * pe + EMA * e[d])
            m_got = _unit((1 - EMA) * _bf16(pe) + EMA * _bf16(e[d]))
            want = dict(oid=pre["ids"][k], version=pre["version"][k] + 1,
                        obs=pre["obs_count"][k] + 1, label=pre["label"][k])
        else:
            cloud = merge(old, pts[d], budget)
            m_ref, m_got = e[d], _bf16(e[d])
            want = dict(oid=pre["next_id"] + rank[d], version=1, obs=1,
                        label=cids[d])
        want["n_points"] = len(cloud)
        for key, v in want.items():
            fault(key, int(getattr(rec, key)[d]) != int(v))
        if not control:
            m_got = rec.embed[d].astype(np.float64)
        out["score_gap"] = max(out["score_gap"],
                               float(np.max(np.abs(m_got - m_ref))))
        n = min(len(cloud), int(rec.n_points[d]))
        got_pts = np.asarray(post_points[d][:n], np.float64)
        got_c = rec.centroid[d].astype(np.float64)
        if control:     # bfloat16 depth in the program's place
            got_pts = merge(old, lift(frame, masks[d], cfg, _bf16),
                            budget)[:n]
            got_c = got_pts.mean(0)
        gap = np.abs(got_pts - cloud[:n])
        out["lift_gap"] = max(out["lift_gap"], float(gap.max(initial=0.0)))
        out["cent_gap"] = max(out["cent_gap"], float(np.max(np.abs(
            got_c - cloud.mean(0)))))
        last_seen[k], obs[k] = index, want["obs"]
    stale = pre["active"] & (index - last_seen > PRUNE_MAX_AGE) \
        & (obs < cfg["min_obs_before_sync"])
    fault("pruned", int(stale.sum()) != int(rec.n_pruned))
    return out


# ---------------------------------------------------------------------------
# the store over time, in object-id space
# ---------------------------------------------------------------------------
class Reference(serve.Reference):
    """The room rebuilt from the keyframes' records.  Slot ``k`` of the
    arrays ``serve.Reference`` replays is object id ``k + 1``, so its
    packet, query and mirror replay apply unchanged; ``ticks[t]`` holds
    (pose time, first keyframe, keyframes) of tick ``t``, and records[i]
    is keyframe i's record (None: nothing detected).  ``pre`` maps a
    sampled keyframe to the program's store columns before it, which the
    replay holds to its own store as it reaches that keyframe."""

    def __init__(self, cfg: dict, trf, records: list, replay_clients,
                 pre: dict = None):
        # ``act`` is what a viewer is owed and a query sees: live objects
        # seen ``min_obs_before_sync`` times; ``live`` is every live object
        # (the prune's and the zone mirror's)
        self.cfg, self.trf = cfg, trf
        self.records = records
        self.pre = pre or {}
        self.rc = np.asarray(replay_clients)
        self.cc = set()                      # rows' contents: not replayed
        nx, nz = cfg["zones"]
        half = cfg["room_m"] / 2
        self.grid = {"x0": -half, "z0": -half,
                     "size": cfg["room_m"] / max(nx, nz), "nx": nx, "nz": nz}
        self.Z = nx * nz
        N = max([int(r.oid[r.slot < cfg["capacity"]].max(initial=0))
                 for r in records if r is not None] + [1])
        self.act = np.zeros(N, bool)
        self.live = np.zeros(N, bool)
        self.dele = np.zeros(N, bool)
        self.ver = np.zeros(N, np.int64)
        self.cent = np.zeros((N, 3), np.float32)
        self.emb = np.zeros((N, cfg["embed_dim"]), np.float32)
        self.lab = np.zeros(N, np.int64)
        self.npt = np.zeros(N, np.int64)
        self.obs = np.zeros(N, np.int64)
        self.seen = np.zeros(N, np.int64)
        self.zone = np.full(N, -1, np.int64)
        self.next_id = 1
        self.faults, self.record_faults = {}, {}
        self.skipped_owing = 0
        self.max_owe_run = 0
        self.eligible = {}          # keyframe -> [(oid, version, zone)]

    def _rfault(self, what: str, n: int = 1) -> None:
        if n:
            self.record_faults[what] = self.record_faults.get(what, 0) + n

    def _apply(self, start: int, n: int, sync, ever):
        cfg = self.cfg
        for i in range(start, start + n):
            if i in self.pre:
                self._check_pre(self.pre[i])
            rec = self.records[i]
            if rec is None:          # nothing detected: nothing dispatched
                continue
            wrote = np.nonzero(rec.slot < cfg["capacity"])[0]
            ins = [d for d in wrote if not rec.matched[d]]
            for d in wrote:
                k = int(rec.oid[d]) - 1
                if not 0 <= k < len(self.live):
                    self._rfault("row without an object id")
                    continue
                if rec.matched[d]:
                    self._rfault("merge into no live object",
                                 int(not self.live[k]))
                    self._rfault("merged version",
                                 int(rec.version[d] != self.ver[k] + 1))
                    self._rfault("merged count",
                                 int(rec.obs[d] != self.obs[k] + 1))
                else:
                    self._rfault("insert id", int(
                        k + 1 != self.next_id + ins.index(d)))
                    self._rfault("insert version",
                                 int(rec.version[d] != 1 or rec.obs[d] != 1))
                self.live[k] = True
                self.ver[k], self.obs[k] = rec.version[d], rec.obs[d]
                self.cent[k], self.emb[k] = rec.centroid[d], rec.embed[d]
                self.lab[k], self.npt[k] = rec.label[d], rec.n_points[d]
                self.seen[k] = i
                zn = int(serve.zone_of(self.cent[k:k + 1], self.grid)[0])
                zo = int(self.zone[k])
                if zo >= 0 and zo != zn:
                    sync[zo][:, k] = 0
                    ever[zo][:, k] = False
                self.zone[k] = zn
            self.next_id += len(ins)
            elig = [(int(rec.oid[d]), int(rec.version[d]),
                     int(self.zone[int(rec.oid[d]) - 1])) for d in wrote
                    if rec.obs[d] >= cfg["min_obs_before_sync"]
                    and rec.oid[d] > 0]
            self.eligible[i] = elig
            stale = self.live & (i - self.seen > PRUNE_MAX_AGE) \
                & (self.obs < cfg["min_obs_before_sync"])
            self._rfault("pruned", int(int(stale.sum()) != rec.n_pruned))
            self.live &= ~stale
            self.act = self.live & (self.obs >= cfg["min_obs_before_sync"])

    def _check_pre(self, pre: dict) -> None:
        """The program's store before a keyframe against the replayed one:
        the same live objects, and each active row's columns bit for bit
        as the last record that wrote it left them."""
        on = np.nonzero(pre["active"])[0]
        k = pre["ids"][on].astype(np.int64) - 1
        ok = (k >= 0) & (k < len(self.live))
        self._rfault("pre-keyframe objects", int(
            not ok.all() or len(np.unique(k)) != len(k)
            or len(k) != int(self.live.sum())))
        on, k = on[ok], k[ok]
        bad = ~self.live[k]
        for col, mine in (("version", self.ver), ("obs_count", self.obs),
                          ("label", self.lab), ("n_points", self.npt),
                          ("last_seen", self.seen)):
            bad |= pre[col][on].astype(np.int64) != mine[k]
        for col, mine in (("centroid", self.cent), ("embed", self.emb)):
            bad |= (pre[col][on] != mine[k]).any(axis=1)
        self._rfault("pre-keyframe rows", int(bad.sum()))
        self._rfault("pre-keyframe next id",
                     int(int(pre["next_id"]) != self.next_id))

    def _mirror_faults(self, zones: list) -> int:
        eligible, self.act = self.act, self.live
        try:
            return super()._mirror_faults(zones)
        finally:
            self.act = eligible


# ---------------------------------------------------------------------------
def verdict(values: dict) -> list:
    """Every compared number beside its limit, in ``LIMITS`` order."""
    return [{"name": k, "value": values[k], "limit": LIMITS[k]}
            for k in LIMITS]


def first_delivery(key, ver, packets, n_keys: int):
    """Wall time of the first framed packet giving (client, oid) ``key``
    version ``ver`` or newer, NaN where none did.  ``key`` is
    client * n_keys + oid."""
    dk, dv, dt = [], [], []
    for p in packets:
        c_idx = np.nonzero(p["counts"])[0]
        if not len(c_idx):
            continue
        v = p["valid"][c_idx]
        cc = np.broadcast_to(c_idx[:, None], v.shape)[v]
        dk.append(cc.astype(np.int64) * n_keys
                  + p["oid"][c_idx][v].astype(np.int64))
        dv.append(p["version"][c_idx][v].astype(np.int64))
        dt.append(np.full(int(v.sum()), p["wall"]))
    out = np.full(len(key), np.nan)
    if not dk:
        return out
    dk, dv, dt = map(np.concatenate, (dk, dv, dt))
    order = np.lexsort((dv, dk))
    dk, dv, dt = dk[order], dv[order], dt[order]
    # the earliest time among deliveries of one key from a version on: a
    # suffix minimum restarted at each key
    first = np.empty_like(dt)
    for s, e in zip(*_runs(dk)):
        first[s:e] = np.minimum.accumulate(dt[s:e][::-1])[::-1]
    lo = np.searchsorted(dk, key, side="left")
    hi = np.searchsorted(dk, key, side="right")
    for m in np.nonzero(hi > lo)[0]:
        a = lo[m] + np.searchsorted(dv[lo[m]:hi[m]], ver[m], side="left")
        if a < hi[m]:
            out[m] = first[a]
    return out


def _runs(sorted_keys):
    edges = np.flatnonzero(np.diff(sorted_keys)) + 1
    return np.r_[0, edges], np.r_[edges, len(sorted_keys)]


def update_latencies(ref: Reference, kf_due: dict, kf_tick: dict, subs,
                     packets, window_t0: float) -> dict:
    """Due time -> first framed packet giving the viewer that version or
    a newer one, over every (viewer, row version) pair of the window's
    keyframes whose row was eligible for the viewer at publish (seen
    ``min_obs_before_sync`` times) and whose zone the viewer was
    subscribed to then.  A pair is left out when, before delivery, the
    viewer leaves that zone or the object moves to another; a pair
    otherwise undelivered is failed."""
    pc, po, pv, pz, pt, pd = [], [], [], [], [], []
    for i, due in kf_due.items():
        t = kf_tick.get(i)
        if t is None:
            continue
        for oid, v, z in ref.eligible.get(i, ()):
            c = np.nonzero(subs[t][:, z])[0]
            pc.append(c)
            po.append(np.full(len(c), oid))
            pv.append(np.full(len(c), v))
            pz.append(np.full(len(c), z))
            pt.append(np.full(len(c), t))
            pd.append(np.full(len(c), due))
    if not pc:
        return {"latency_ms": [], "due_s": [], "n_pairs": 0, "failed": 0,
                "dropped": 0}
    pc, po, pv, pz, pt, pd = map(np.concatenate, (pc, po, pv, pz, pt, pd))
    n_keys = len(ref.act) + 1
    got = first_delivery(pc * n_keys + po, pv, packets, n_keys)
    hit = np.isfinite(got)
    lat = ((got[hit] - (window_t0 + pd[hit])) * 1e3).tolist()
    dropped = 0
    for m in np.nonzero(~hit)[0]:
        c, z, t = int(pc[m]), int(pz[m]), int(pt[m])
        left = not subs[t:, c, z].all()
        moved = int(ref.zone[int(po[m]) - 1]) != z
        dropped += int(left or moved)
    miss = int((~hit).sum())
    return {"latency_ms": lat, "due_s": pd[hit].tolist(),
            "n_pairs": len(pc) - dropped, "failed": miss - dropped,
            "dropped": dropped}
