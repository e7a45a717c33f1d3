"""The serving cells' load generator: a child process that never touches
the chip.

    python bench/harness/loadgen.py '<spec json>'

It speaks to its parent by lines on stdin/stdout:

    parent -> {"port": p}     connect to the `FleetServer` on localhost
    child  -> {"event": "ready"}     after a warm-up round of frames
    parent -> {"go": true}    the window starts now
    child  -> {"event": "window_end"}     `seconds` later
    child  -> {"event": "result", ...}    after every answer came back (or
                                          a minute passed), compared with
                                          the plain reference

Traffic comes from a mix file (see `bench/traffic/`), the readings from
the benchmark's copy of the dataset generator, all drawn from the spec's
seed:

* `closed`: every tenant keeps `frames_per_replica` x its replicas frames
  of `frame_rows` readings outstanding; a slot's next frame is due when
  every answer of its last one is in.
* `open`: `rate_frames_per_s` x `seconds` frames at arrival times drawn
  uniformly over the window (a Poisson process given its count), spread
  over the tenants in proportion to Zipf weights over a fixed order and shuffled,
  so every seed offers the same work in another order.

A reading's latency runs from its frame's scheduled send time to the
moment its label reached this process; the reader thread stamps every
result message as it arrives.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parents[1]
DRAIN_S = 60.0
WARMUP_ROUNDS = 2


def _emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


def _read() -> dict:
    line = sys.stdin.readline()
    if not line:
        raise SystemExit("parent closed the pipe")
    return json.loads(line)


def zipf_counts(n_frames: int, order: list[str], s: float) -> dict:
    """Frames per tenant: Zipf(s) shares over `order`, largest remainder."""
    w = 1.0 / np.arange(1, len(order) + 1) ** s
    share = w / w.sum() * n_frames
    counts = np.floor(share).astype(int)
    for i in np.argsort(-(share - counts), kind="stable")[
            : n_frames - counts.sum()]:
        counts[i] += 1
    return dict(zip(order, counts.tolist()))


def open_schedule(traffic: dict, seconds: float, seed: int
                  ) -> list[tuple[float, str]]:
    """(offset seconds, tenant) of every frame of an open-loop window."""
    rng = np.random.default_rng([seed, 1])
    n = int(round(traffic["rate_frames_per_s"] * seconds))
    counts = zipf_counts(n, traffic["zipf_order"], traffic["zipf_s"])
    tenants = np.array([t for t, c in counts.items() for _ in range(c)])
    rng.shuffle(tenants)
    times = np.sort(rng.uniform(0.0, seconds, size=n))
    return list(zip(times.tolist(), tenants.tolist()))


class Frame:
    __slots__ = ("tenant", "rows", "t_sched", "t_send", "handles")

    def __init__(self, tenant, rows, t_sched, t_send, handles):
        self.tenant, self.rows = tenant, rows
        self.t_sched, self.t_send, self.handles = t_sched, t_send, handles


def main(spec: dict) -> None:
    sys.path[:0] = [str(BENCH_DIR), str(BENCH_DIR.parent / "src")]
    from harness import reference as R
    from repro.serve import protocol as P
    from repro.serve.client import FleetClient

    class StampingClient(FleetClient):
        """Records when each result message reached this process."""

        stamps: list

        def _on_message(self, msg):
            t = time.perf_counter()
            if msg.type == P.MSG_RESULT_BATCH:
                self.stamps.append((t, np.asarray(msg.req_ids, np.int64)))
            elif msg.type in (P.MSG_RESULT, P.MSG_SHED, P.MSG_ERROR):
                self.stamps.append((t, np.array([msg.req_id], np.int64)))
            super()._on_message(msg)

    seed, seconds = int(spec["seed"]), float(spec["seconds"])
    traffic, rows_per = spec["traffic"], int(spec["traffic"]["frame_rows"])
    tenants = spec["tenants"]
    pools = {}
    for name in tenants:
        ds = R.make_dataset(name, seed)
        pools[name] = np.concatenate([ds.x_train, ds.x_test]).astype(
            np.float64)

    port = int(_read()["port"])
    client = StampingClient("127.0.0.1", port)
    client.stamps = []
    frames: list[Frame] = []
    lock = threading.Lock()

    def send(name: str, rng, t_sched: float) -> Frame:
        rows = rng.integers(0, pools[name].shape[0], size=rows_per)
        t_send = time.perf_counter()
        handles = client.submit_many(name, pools[name][rows])
        return Frame(name, rows, t_sched, t_send, handles)

    def wait_all(frame: Frame, deadline: float) -> None:
        for h in frame.handles:
            if not h.done():
                h._event.wait(max(0.0, deadline - time.perf_counter()))

    warm_rng = np.random.default_rng([seed, 2])
    for _ in range(WARMUP_ROUNDS):
        warm = [send(n, warm_rng, time.perf_counter())
                for n in tenants for _ in range(spec["replicas"])]
        for f in warm:
            wait_all(f, time.perf_counter() + DRAIN_S)
    _emit({"event": "ready"})
    _read()                                   # go
    t0 = time.perf_counter()
    t_end = t0 + seconds
    client.stamps.clear()
    stop = threading.Event()

    if traffic["loop"] == "closed":
        slots = int(traffic["frames_per_replica"]) * int(spec["replicas"])

        def slot(name: str, k: int) -> None:
            # a frame is due the moment its slot's last one is answered;
            # how late it leaves is the generator's own delay
            rng = np.random.default_rng([seed, 3, tenants.index(name), k])
            due = t0
            while not stop.is_set():
                f = send(name, rng, due)
                with lock:
                    frames.append(f)
                wait_all(f, time.perf_counter() + DRAIN_S)
                due = time.perf_counter()

        threads = [threading.Thread(target=slot, args=(n, k), daemon=True)
                   for n in tenants for k in range(slots)]
        for th in threads:
            th.start()
        time.sleep(max(0.0, t_end - time.perf_counter()))
        stop.set()
        _emit({"event": "window_end"})
        for th in threads:
            th.join(DRAIN_S)
    else:
        rng = np.random.default_rng([seed, 4])
        for off, name in open_schedule(traffic, seconds, seed):
            t_sched = t0 + off
            delay = t_sched - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            frames.append(send(name, rng, t_sched))
        time.sleep(max(0.0, t_end - time.perf_counter()))
        _emit({"event": "window_end"})
        deadline = time.perf_counter() + DRAIN_S
        for f in frames:
            wait_all(f, deadline)
    client.close()
    _emit({"event": "result", **judge(frames, client.stamps, t0, t_end,
                                      tenants, pools, spec, R)})


def judge(frames, stamps, t0, t_end, tenants, pools, spec, R) -> dict:
    """Compare every answer with the reference; latency and lateness."""
    if stamps:
        ids = np.concatenate([s[1] for s in stamps])
        ts = np.concatenate([np.full(len(s[1]), s[0]) for s in stamps])
        order = np.argsort(ids, kind="stable")
        ids, ts = ids[order], ts[order]
    else:
        ids, ts = np.zeros(0, np.int64), np.zeros(0)
    ref = {n: R.tnn_labels(R.seeded_weights(n), pools[n]) for n in tenants}
    control = spec.get("control")
    if control:                 # the reference in the program's place
        labels_of = {n: R.tnn_labels(R.seeded_weights(n), pools[n],
                                     dtype=control) for n in tenants}
    n_off = n_mis = n_miss = n_fail = n_win = 0
    lat, frame_ms = [], []
    for f in frames:
        got = np.array([-1 if h.label is None or h.error or h.shed
                        else h.label for h in f.handles], dtype=np.int64)
        if control:
            got = labels_of[f.tenant][f.rows].astype(np.int64)
        rid = np.array([h.req_id for h in f.handles], dtype=np.int64)
        t_done = np.full(len(rid), np.inf)
        if len(ids):
            pos = np.minimum(np.searchsorted(ids, rid), len(ids) - 1)
            hit = (ids[pos] == rid) & (got >= 0)
            t_done[hit] = ts[pos[hit]]
        want = ref[f.tenant][f.rows]
        failed = np.array([h.error is not None or h.shed
                           for h in f.handles])
        n_off += len(got)
        n_fail += int(failed.sum())
        n_miss += int(((got < 0) & ~failed).sum())
        n_mis += int(((got >= 0) & (got != want)).sum())
        n_win += int(((got == want) & (t_done <= t_end)).sum())
        if f.t_sched <= t_end:
            lat.append((t_done - f.t_sched) * 1e3)
            frame_ms.append(lat[-1].max())
    lat = np.sort(np.concatenate(lat)) if lat else np.zeros(1)
    late = np.array([(f.t_send - f.t_sched) * 1e3 for f in frames] or [0.0])
    finite = np.isfinite(lat)

    def q(p: float) -> float:   # nearest rank; unanswered readings count
        v = lat[min(len(lat) - 1, int(np.ceil(p * len(lat))) - 1)]
        return float(v) if np.isfinite(v) else float(
            lat[finite].max() if finite.any() else DRAIN_S * 1e3)

    quarter = max(1, len(frame_ms) // 4)    # a growing backlog shows as
    head = float(np.median(frame_ms[:quarter] or [0.0]))   # later frames
    tail = float(np.median(frame_ms[-quarter:] or [0.0]))  # waiting longer
    return {"n_frames": len(frames), "attempted": n_off, "failed": n_fail,
            "first_quarter_ms": head, "last_quarter_ms": tail,
            "mismatched": n_mis, "missing": n_miss, "correct_in_window": n_win,
            "p50_ms": q(0.50), "p99_ms": q(0.99), "max_ms": q(1.0),
            "late_p99_ms": float(np.percentile(late, 99)),
            "late_max_ms": float(late.max()),
            "window_s": t_end - t0}


if __name__ == "__main__":
    os.environ["JAX_PLATFORMS"] = "cpu"
    main(json.loads(sys.argv[1]))
