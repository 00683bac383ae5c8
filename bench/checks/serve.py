"""Plain reference for the ``serve`` driver: a numpy replay of the venue.

It imports nothing of the program.  From the run's own traffic (the seed's
store, the ingest rows handed out at each tick, each tick's poses and
subscriptions) it rebuilds the published store tick by tick and checks,
against what the timed loop produced:

- the query engine: each sampled query's top-k (ids and scores) against a
  float64 flat sweep of the store the query was served from;
- the session collect: for the sampled clients, every packet of every
  zone from the first tick on.  The reference keeps each client's synced
  version and ever-shipped flag per zone and per object, and requires of
  each packet the changed rows of the zone (live rows newer than the
  client's version; tombstones of rows once shipped to it), the budget's
  count of them taken by priority (proximity to the client, tombstones
  first), their versions, and the exact wire bytes.  On every
  ``CONTENT_EVERY``-th tick it also checks each sampled client's rows
  field by field: embedding, label, point count, the stride-downsampled
  float16 points and the centroid of those points;
- the zone mirror: after the run, each zone's rows against the final store
  routed by centroid.

Where the reference's float64 priority leaves two rows within
``PRI_TIE`` of the budget's cut, either may ship; the replay then follows
the rows that did.
"""
from __future__ import annotations

import numpy as np

HEADER_B = 24           # wire: id 4, label 2, version 4, n_points 2,
#                         centroid 12
TOMB_B = 9              # wire: a tombstone row
POINT_B = 6             # wire: one float16 point
SCORE_TIE = 1e-5        # query scores this close may swap ranks
PRI_TIE = 1e-5          # priorities this close may swap at the budget's cut

# limits of the compared numbers: see PERF.md for the readings they were
# set from
LIMITS = {"query_score_gap": 3e-5, "query_faults": 0, "packet_faults": 0,
          "centroid_gap_m": 2e-5, "mirror_faults": 0,
          "unanswered_queries": 0, "undelivered_pairs": 0}
MAX_OWE_SKIP = 1        # ticks in a row a zone may go uncollected while
#                         it owes a client rows


def zone_of(cent: np.ndarray, grid: dict) -> np.ndarray:
    """XZ grid cell of float32 centroids; outside the grid clamps to the
    border cell (the deployment's zone rule)."""
    c = np.asarray(cent, np.float32)
    ix = np.clip(((c[:, 0] - grid["x0"]) // grid["size"]).astype(np.int64),
                 0, grid["nx"] - 1)
    iz = np.clip(((c[:, 2] - grid["z0"]) // grid["size"]).astype(np.int64),
                 0, grid["nz"] - 1)
    return ix * grid["nz"] + iz


def oracle_topk(active, embed, centroid, q, center, radius, k, dtype):
    """Flat sweep: slots active and within ``radius`` of ``center``, cosine
    score in ``dtype`` (float64 for the reference, bfloat16 inputs for the
    control), descending, stable; k + 1 ranks padded with (-1, -inf)."""
    ok = np.asarray(active, bool) & (np.linalg.norm(
        centroid.astype(np.float64) - center.astype(np.float64), axis=1)
        <= float(radius))
    if dtype == "bfloat16":
        import ml_dtypes
        e = embed.astype(ml_dtypes.bfloat16).astype(np.float32)
        sim = (e @ np.asarray(q).astype(ml_dtypes.bfloat16)
               .astype(np.float32)).astype(np.float64)
    else:
        sim = embed.astype(np.float64) @ np.asarray(q, np.float64)
    sim[~ok] = -np.inf
    order = np.argsort(-sim, kind="stable")[:k + 1]
    slots = np.where(np.isfinite(sim[order]), order, -1)
    return slots, sim[order]


def topk_compare(got_ids, got_scores, want_ids, want_scores):
    """(score gap, faults): the largest gap between finite scores rank by
    rank, and the ranks whose ids differ where the reference has no
    neighbouring rank within ``SCORE_TIE`` (or where finiteness differs)."""
    k = len(got_ids)
    gs = np.asarray(got_scores, np.float64)
    ws = np.asarray(want_scores, np.float64)
    fin = np.isfinite(ws[:k])
    faults = int((np.isfinite(gs) != fin).sum())
    both = fin & np.isfinite(gs)
    gap = float(np.max(np.abs(gs[both] - ws[:k][both]))) if both.any() \
        else 0.0
    for i in range(k):
        if not fin[i] or int(got_ids[i]) == int(want_ids[i]):
            continue
        if not any(0 <= j < len(ws) and np.isfinite(ws[j])
                   and abs(ws[j] - ws[i]) <= SCORE_TIE
                   for j in (i - 1, i + 1)):
            faults += 1
    return gap, faults


def downsample(points: np.ndarray, n: int, budget: int, P: int):
    """Stride downsample of one cloud to ``budget`` points: output i takes
    source point floor(i * n / budget) when n > budget."""
    n = max(int(n), 1)
    ar = np.arange(budget)
    sub = (ar * n) // budget if n > budget else ar
    sub = np.minimum(sub, P - 1)
    n_out = min(n, budget)
    out = points[sub].astype(np.float32)
    out[ar >= n_out] = 0.0
    return out, n_out


def f16_equal(got, want_f32) -> bool:
    """Float16 wire points equal to the float32 source rounded to float16,
    where a value in float16's subnormal range may also arrive as zero (a
    TPU flushes subnormals)."""
    got = np.asarray(got, np.float16)
    want = np.asarray(want_f32, np.float32).astype(np.float16)
    tiny = np.abs(want) < np.finfo(np.float16).smallest_normal
    return bool(np.all((got == want) | (tiny & (got == 0))))


class Reference:
    """The venue rebuilt from the run's traffic."""

    def __init__(self, cfg: dict, trf, init: dict, init_points, stream: dict,
                 replay_clients, content_clients):
        self.cfg, self.trf = cfg, trf
        self.init_points = init_points       # slots -> [n, P, 3] float32
        self.stream = stream
        self.rc = np.asarray(replay_clients)
        self.cc = set(int(c) for c in content_clients)
        nx, nz = cfg["zones"]
        half = cfg["room_m"] / 2
        self.grid = {"x0": -half, "z0": -half,
                     "size": cfg["room_m"] / max(nx, nz), "nx": nx, "nz": nz}
        self.Z = nx * nz
        self.act = init["active"].copy()
        self.dele = np.zeros_like(self.act)
        self.ver = init["version"].astype(np.int64)
        self.cent = init["centroid"].astype(np.float32)
        self.emb = init["embed"].astype(np.float32)
        self.lab = init["label"].astype(np.int64)
        self.npt = init["n_points"].astype(np.int64)
        self.src = np.full(self.act.shape, -1, np.int64)   # -1: initial
        self.zone = np.where(self.act, zone_of(self.cent, self.grid), -1)
        self.faults = {}                     # what failed, how often
        self.skipped_owing = 0
        self.max_owe_run = 0
        R = len(stream["slots"])
        self.row_version = np.zeros(R, np.int64)
        self.row_zone = np.full(R, -1, np.int64)

    def _fault(self, what: str, n: int = 1) -> None:
        if n:
            self.faults[what] = self.faults.get(what, 0) + n

    def _ok(self, cond: bool, what: str) -> bool:
        self._fault(what, int(not cond))
        return bool(cond)

    # ------------------------------------------------------------------
    def _apply(self, start: int, n: int, sync, ever):
        s = self.stream
        for j in range(start, start + n):
            k = int(s["slots"][j])
            self.ver[k] += 1
            if s["tomb"][j]:
                self.act[k], self.dele[k], self.npt[k] = False, True, 0
            else:
                self.act[k], self.dele[k] = True, False
                self.emb[k] = s["embed"][j]
                self.lab[k] = s["label"][j]
                self.npt[k] = s["n_points"][j]
                self.cent[k] = s["centroid"][j]
                self.src[k] = j
            zn = int(zone_of(self.cent[k:k + 1], self.grid)[0])
            zo = int(self.zone[k])
            if zo >= 0 and zo != zn:
                # the object left zone zo: its slot there is freed, and no
                # client keeps a version of it
                sync[zo][:, k] = 0
                ever[zo][:, k] = False
            self.zone[k] = zn
            self.row_version[j] = self.ver[k]
            self.row_zone[j] = zn

    def _points_of(self, k: int):
        """(kind, index) of slot ``k``'s current cloud."""
        j = int(self.src[k])
        return ("init", k) if j < 0 else ("pool", j % len(
            self.stream["pool"]))

    # ------------------------------------------------------------------
    def replay(self, *, ticks, subs, zones_started, packets, queries,
               zones, control: bool = False) -> dict:
        """Replay every tick; return the compared numbers it reads, by
        name (``verdict`` puts each beside its limit).  ``queries`` maps a
        query index to (serve tick, program result); with ``control`` the
        reference's own bfloat16 sweep stands in for the program's query
        answers and centroids."""
        cfg, trf = self.cfg, self.trf
        E, Pc, P = cfg["embed_dim"], cfg["client_points"], \
            cfg["server_points"]
        budget, w = cfg["budget_rows"], cfg["priority"]["proximity_weight"]
        N, S = len(self.act), len(self.rc)
        sync = [np.zeros((S, N), np.int64) for _ in range(self.Z)]
        ever = [np.zeros((S, N), bool) for _ in range(self.Z)]
        owe_run = np.zeros((self.Z, S), np.int64)   # ticks owed, no collect
        by_tick = {}
        for p in packets:
            by_tick.setdefault(p["tick"], {})[p["zone"]] = p
        q_by_tick = {}
        for i, (t, res) in queries.items():
            q_by_tick.setdefault(t, []).append((i, res))
        prev = np.zeros((S, self.Z), bool)
        n_rows_checked, n_pk_checked = 0, 0
        q_gap, q_faults, cent_gap = 0.0, 0, 0.0
        content = []          # (kind, index, n_out, program points)
        for t in range(len(ticks)):
            tau = float(ticks[t][0])
            sub = subs[t]
            poses = trf.poses_at(tau).astype(np.float64)
            left = prev & ~sub[self.rc]
            for i, z in zip(*np.nonzero(left)):
                sync[z][i] = 0
                ever[z][i] = False
            started = set(zones_started.get(t, ()))
            for z in range(self.Z):
                m = np.nonzero(self.zone == z)[0]          # zone members
                pk = by_tick.get(t, {}).get(z)
                if z in started:
                    if pk is None:
                        self._fault("zone collected, no packet framed")
                        continue
                    # unsubscribed clients get nothing from this zone
                    self._fault("rows to an unsubscribed client", int(
                        (pk["counts"][~sub[:, z]] != 0).sum()))
                newer = self.ver[m][None] > sync[z][:, m]  # [S, members]
                live_all = self.act[m][None] & newer
                tomb_all = self.dele[m][None] & ever[z][:, m] & newer
                for i, c in enumerate(self.rc):
                    if not sub[c, z]:
                        owe_run[z][i] = 0
                        continue
                    changed = np.zeros(N, bool)
                    tomb = np.zeros(N, bool)
                    changed[m] = live_all[i] | tomb_all[i]
                    tomb[m] = tomb_all[i]
                    n_changed = int(changed.sum())
                    if z not in started:
                        # the overlapped loop skips a zone for one tick
                        # after a collect that shipped rows (its dirty mark
                        # returns when that collect is framed a tick
                        # later); a second tick in a row with rows owed
                        # and no collect breaks the delivery guarantee
                        run = int(owe_run[z][i]) + 1 if n_changed else 0
                        owe_run[z][i] = run
                        self.skipped_owing += int(run > 0)
                        self.max_owe_run = max(self.max_owe_run, run)
                        self._fault("rows owed, zone not collected",
                                    int(run > MAX_OWE_SKIP))
                        continue
                    owe_run[z][i] = 0
                    n_pk_checked += 1
                    cnt = int(pk["counts"][c])
                    valid = pk["valid"][c]
                    slots = pk["oid"][c][valid].astype(np.int64) - 1
                    vers = pk["version"][c][valid].astype(np.int64)
                    ok = self._ok(cnt == min(budget, n_changed),
                                  "row count") \
                        and self._ok(len(slots) == cnt
                                     and bool(valid[:cnt].all())
                                     and bool(((slots >= 0)
                                               & (slots < N)).all()),
                                     "valid rows")
                    ok = ok and self._ok(bool(changed[slots].all()),
                                         "row not owed") \
                        and self._ok(bool((vers == self.ver[slots]).all()),
                                     "stale version")
                    if ok and cnt < n_changed:
                        d = np.linalg.norm(self.cent[m].astype(np.float64)
                                           - poses[c], axis=1)
                        pri = np.full(N, -np.inf)
                        pri[m] = np.where(tomb[m], 1e30, w / (1.0 + d))
                        rest = changed.copy()
                        rest[slots] = False
                        ok = self._ok(pri[slots].min()
                                      >= pri[rest].max() - PRI_TIE,
                                      "priority order")
                    if ok:
                        is_tomb = tomb[slots]
                        n_out = np.minimum(np.maximum(self.npt[slots], 1), Pc)
                        want_b = int(((HEADER_B + 2 * E + POINT_B * n_out)
                                      * ~is_tomb).sum()
                                     + TOMB_B * is_tomb.sum())
                        ok = self._ok(want_b == int(pk["nbytes"][c]),
                                      "wire bytes")
                    if ok and pk["content"] is not None and int(c) in self.cc:
                        r = sorted(self.cc).index(int(c))
                        ct = pk["content"]
                        for u, k in enumerate(slots):
                            n_rows_checked += 1
                            tb = bool(is_tomb[u])
                            no = 0 if tb else int(n_out[u])
                            row_ok = (int(ct.oid[r, u]) == k + 1
                                      and bool(ct.deleted[r, u]) == tb
                                      and int(ct.label[r, u]) == self.lab[k]
                                      and int(ct.n_points[r, u]) == no
                                      and np.array_equal(ct.embed[r, u],
                                                         self.emb[k]))
                            if tb:
                                row_ok = row_ok and np.array_equal(
                                    ct.centroid[r, u], self.cent[k]) \
                                    and not ct.points[r, u].any()
                            else:
                                kind, idx = self._points_of(k)
                                content.append((kind, idx, int(self.npt[k]),
                                                ct.points[r, u],
                                                ct.centroid[r, u]))
                            self._fault("row contents", int(not row_ok))
                    if valid.any():
                        sync[z][i][slots] = vers
                        ever[z][i][slots] = True
            for qi, res in q_by_tick.get(t, ()):
                want_s, want_v = oracle_topk(
                    self.act, self.emb, self.cent, trf.q_embed[qi],
                    trf.q_center[qi], trf.radius, trf.k, "float64")
                if control:
                    gs, gv = oracle_topk(
                        self.act, self.emb, self.cent, trf.q_embed[qi],
                        trf.q_center[qi], trf.radius, trf.k, "bfloat16")
                    got_ids = np.where(gs[:trf.k] >= 0, gs[:trf.k] + 1, 0)
                    got_sc = gv[:trf.k]
                else:
                    got_ids, got_sc = np.asarray(res.oids), \
                        np.asarray(res.scores)
                want_ids = np.where(want_s >= 0, want_s + 1, 0)
                gap, f = topk_compare(got_ids, got_sc, want_ids, want_v)
                q_gap, q_faults = max(q_gap, gap), q_faults + f
            start, n = int(ticks[t][1]), int(ticks[t][2])
            self._apply(start, n, sync, ever)
            prev = sub[self.rc]

        # the sampled rows' points, against the clouds they came from
        inits = sorted({idx for kind, idx, *_ in content if kind == "init"})
        ipts = dict(zip(inits, self.init_points(inits))) if inits else {}
        for kind, idx, n_src, got_pts, got_cent in content:
            src = ipts[idx] if kind == "init" else self.stream["pool"][idx]
            want, n_out = downsample(src, n_src, Pc, P)
            if control:
                import ml_dtypes
                got_cent = want[:n_out].astype(ml_dtypes.bfloat16).astype(
                    np.float32).mean(axis=0, dtype=np.float32)
            self._fault("row points", int(not f16_equal(got_pts, want)))
            c_want = want[:n_out].astype(np.float64).mean(axis=0)
            cent_gap = max(cent_gap, float(np.max(np.abs(
                np.asarray(got_cent, np.float64) - c_want))))
        mirror = self._mirror_faults(zones)
        self.counts = {"packets_checked": n_pk_checked,
                       "rows_checked": n_rows_checked,
                       "queries_checked": len(queries),
                       "zone_rows_checked": self.zone_rows_checked,
                       "zone_ticks_skipped_owing": self.skipped_owing,
                       "max_ticks_owed_uncollected": self.max_owe_run,
                       "mirror_faults": self.mirror_why}
        return {"query_score_gap": q_gap, "query_faults": q_faults,
                "packet_faults": sum(self.faults.values()),
                "centroid_gap_m": cent_gap, "mirror_faults": mirror}

    # ------------------------------------------------------------------
    def _mirror_faults(self, zones: list) -> int:
        """Each zone's occupied rows against the final store routed by
        centroid: the same objects, versions, state and contents; the
        sampled rows' points too."""
        why, checked = {}, 0

        def fault(what, n):
            if n:
                why[what] = why.get(what, 0) + int(n)

        for z, zs in enumerate(zones):
            occ = zs["active"] | zs["deleted"]
            ids = zs["ids"][occ].astype(np.int64)
            want = np.nonzero((self.zone == z) & (self.act | self.dele))[0]
            if sorted(ids.tolist()) != (want + 1).tolist():
                fault("objects in zone", len(set(ids.tolist())
                                             ^ set((want + 1).tolist())))
                continue
            zslots = np.nonzero(occ)[0]
            k = ids - 1
            live = self.act[k]
            checked += len(k)
            fault("version", (zs["version"][zslots] != self.ver[k]).sum())
            fault("active", (zs["active"][zslots] != live).sum())
            fault("label", (zs["label"][zslots][live] != self.lab[k][live])
                  .sum())
            fault("n_points", (zs["n_points"][zslots] != self.npt[k]).sum())
            fault("centroid", (zs["centroid"][zslots] != self.cent[k])
                  .any(axis=1).sum())
            fault("embed", (zs["embed"][zslots][live] != self.emb[k][live])
                  .any(axis=1).sum())
            for row, pts in zs.get("points_sample", {}).items():
                kk = int(zs["ids"][row]) - 1
                kind, idx = self._points_of(kk)
                src = self.init_points([idx])[0] if kind == "init" \
                    else self.stream["pool"][idx]
                fault("points", not np.array_equal(pts, src))
        self.zone_rows_checked = checked
        self.mirror_why = why
        return sum(why.values())


# ---------------------------------------------------------------------------
def verdict(values: dict) -> list:
    """Every compared number beside its limit, in ``LIMITS`` order."""
    return [{"name": k, "value": values[k], "limit": LIMITS[k]}
            for k in LIMITS]


def update_latencies(ref: Reference, trf, ticks, subs, packets,
                     window_t0: float) -> dict:
    """Due time -> first framed packet giving the client that version or a
    newer one, over every (client, upsert row of the window) pair whose
    client was subscribed to the row's zone at publish.  A pair is left
    out when, before delivery, its client leaves that zone or the object
    is tombstoned or moves to another zone; a pair otherwise undelivered
    is failed."""
    N = len(ref.act)
    T = len(ticks)
    R = len(trf.row_due)
    row_tick = np.full(R, -1, np.int64)
    for t in range(T):
        s, n = int(ticks[t][1]), int(ticks[t][2])
        row_tick[s:s + n] = t
    rows = np.arange(trf.n_warm, R)
    rows = rows[(row_tick[rows] >= 0) & ~trf.row_tomb[rows]]
    pc, pj = [], []
    for j in rows:
        c = np.nonzero(subs[row_tick[j]][:, ref.row_zone[j]])[0]
        pc.append(c)
        pj.append(np.full(len(c), j))
    pc = np.concatenate(pc) if pc else np.zeros(0, np.int64)
    pj = np.concatenate(pj) if pj else np.zeros(0, np.int64)
    slot = trf.row_slot[pj].astype(np.int64)
    key = pc * (N + 1) + slot + 1
    # deliveries: (client, oid, version, framed wall time)
    dk, dv, dt = [], [], []
    for p in packets:
        c_idx = np.nonzero(p["counts"])[0]
        if not len(c_idx):
            continue
        v = p["valid"][c_idx]
        cc = np.broadcast_to(c_idx[:, None], v.shape)[v]
        dk.append(cc.astype(np.int64) * (N + 1)
                  + p["oid"][c_idx][v].astype(np.int64))
        dv.append(p["version"][c_idx][v].astype(np.int64))
        dt.append(np.full(int(v.sum()), p["wall"]))
    dk = np.concatenate(dk) if dk else np.zeros(0, np.int64)
    dv = np.concatenate(dv) if dv else np.zeros(0, np.int64)
    dt = np.concatenate(dt) if dt else np.zeros(0)
    VB = 1 << 24
    comp = dk * VB + dv
    order = np.lexsort((dt, comp))
    comp, dk, dt = comp[order], dk[order], dt[order]
    # earliest delivery of a version >= v within each (client, oid) group:
    # a suffix minimum that never crosses into an earlier group
    span = (dt.max() - dt.min() + 1.0) if len(dt) else 1.0
    ranked = np.unique(dk, return_inverse=True)[1] if len(dk) else dk
    adj = dt - dt.min() + ranked * span if len(dt) else dt
    suf = np.minimum.accumulate(adj[::-1])[::-1] - ranked * span \
        + (dt.min() if len(dt) else 0.0)
    want = key * VB + ref.row_version[pj]
    pos = np.searchsorted(comp, want, side="left")
    hit = pos < len(comp)
    hit[hit] = dk[pos[hit]] == key[hit]
    got_t = np.where(hit, suf[np.minimum(pos, max(len(suf) - 1, 0))]
                     if len(suf) else 0.0, np.nan)
    due = window_t0 + trf.row_due[pj]
    lat = (got_t[hit] - due[hit]) * 1e3
    # undelivered pairs: left out, or failed
    miss = np.nonzero(~hit)[0]
    last_off = {}
    dropped = 0
    later = {}
    for j in rows:
        later.setdefault(int(trf.row_slot[j]), []).append(int(j))
    for m in miss:
        c, j = int(pc[m]), int(pj[m])
        z, t0 = int(ref.row_zone[j]), int(row_tick[j])
        cz = (c, z)
        if cz not in last_off:
            off = np.nonzero(~subs[:, c, z])[0]
            last_off[cz] = int(off.max()) if len(off) else -1
        moved = any(trf.row_tomb[j2] or ref.row_zone[j2] != z
                    for j2 in later[int(trf.row_slot[j])] if j2 > j)
        if last_off[cz] > t0 or moved:
            dropped += 1
    return {"latency_ms": lat.tolist(), "n_pairs": len(pj) - dropped,
            "failed": len(miss) - dropped, "dropped": dropped}
