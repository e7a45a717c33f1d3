"""Sensor-stream classification serving on compiled circuit programs.

The execution layer of the `repro.serve` stack (formerly
`repro.serving.circuit_engine`, folded in when the serving layers were
unified): there is no decode loop — every request is one sensor reading
classified in a single circuit pass — so the engine's entire job is
batching.  Queued readings are gathered in arrival order into fixed-shape
padded batches (`max_batch` rows, so the jitted SWAR program compiles
exactly one shape), dispatched as one bit-packed evaluation, and the
labels are scattered back with per-request latency.  At 32 readings per
machine word a single dispatch of a `max_batch=1024` engine costs ~32
word-ops per gate, which is what lets a software model of a 5 Hz printed
circuit serve readings at MHz-equivalent rates.

`classify_stream` is the bulk path (one numpy array in, labels out);
`submit`/`flush` is the request-queue path with per-request bookkeeping.
Both feed the same `ServeStats` (readings/s + batch/request latency
percentiles + SLO-violation and admission-shed counters).  The queue path
is thread-safe: producers may `submit` while another thread flushes, and
concurrent `flush` calls partition the queue instead of double-dispatching
it — the contract `repro.serve.ClassifierFleet`'s dispatch threads rely
on.  A fleet tenant runs N of these engines as a replica pool
(`serve/replicas.py`), each pinned to its own device slice.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass

import numpy as np

from repro import obs
from repro.compile.program import CircuitProgram

STATS_WINDOW = 4096


class _Ring:
    """Fixed-capacity ring of float samples (keeps the most recent N).

    Long-running streams push one batch sample per dispatch; an unbounded
    list grows without limit (and made every percentile call slower), so
    percentiles are computed over a sliding window instead.  Totals that
    must stay exact (counts, busy seconds) live outside the ring.
    """

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("ring capacity must be >= 1")
        self._buf = np.zeros(capacity, dtype=np.float64)
        self._pushed = 0

    def push(self, v: float) -> None:
        self._buf[self._pushed % self._buf.shape[0]] = v
        self._pushed += 1

    def extend(self, values: np.ndarray) -> None:
        """`push` each of `values` in order, in one vector write."""
        cap = self._buf.shape[0]
        kept = values[-cap:]
        first = self._pushed + len(values) - len(kept)
        self._buf[(first + np.arange(len(kept))) % cap] = kept
        self._pushed += len(values)

    def __len__(self) -> int:
        return min(self._pushed, self._buf.shape[0])

    @property
    def total_pushed(self) -> int:
        return self._pushed

    def values(self) -> np.ndarray:
        return self._buf[: len(self)]

    def percentile(self, q: float) -> float:
        return float(np.percentile(self.values(), q)) if len(self) else 0.0

    def max(self) -> float:
        return float(self.values().max()) if len(self) else 0.0


class ServeStats:
    """Throughput + latency accounting for one engine (or a whole fleet).

    Batch samples (one per dispatch) and request samples (one per queued
    request) are kept in bounded rings of `window` entries, so a stream of
    millions of readings holds stats memory constant; counters and busy
    time are exact over the full stream.  `n_shed` counts submissions the
    admission controller rejected (they never enter the request rings, so
    p50/p99 describe *accepted* traffic only).  Thread-safe: dispatch
    threads and stat readers may interleave freely.
    """

    def __init__(self, window: int = STATS_WINDOW):
        self.window = window
        self.n_readings = 0
        self.n_batches = 0
        self.busy_s = 0.0                 # time spent inside dispatches
        self.n_requests = 0
        self.n_slo_miss = 0               # requests finishing past deadline
        self.n_shed = 0                   # submissions refused at admission
        self.batch_ms = _Ring(window)     # per-dispatch wall time
        self.request_ms = _Ring(window)   # per-request submit -> label
        self._lock = threading.Lock()

    def record(self, n: int, dt_s: float) -> None:
        with self._lock:
            self.n_readings += n
            self.n_batches += 1
            self.busy_s += dt_s
            self.batch_ms.push(dt_s * 1e3)

    def record_request(self, latency_ms: float,
                       deadline_ms: float | None = None) -> None:
        with self._lock:
            self.n_requests += 1
            self.request_ms.push(latency_ms)
            if deadline_ms is not None and latency_ms > deadline_ms:
                self.n_slo_miss += 1

    def record_requests(self, latencies_ms: np.ndarray,
                        deadline_ms=None) -> None:
        """`record_request` for a whole batch under one lock; `deadline_ms`
        is None, one budget, or one per request (NaN: none)."""
        lat = np.asarray(latencies_ms, dtype=np.float64).reshape(-1)
        miss = (0 if deadline_ms is None else int(np.count_nonzero(
            lat > np.asarray(deadline_ms, dtype=np.float64))))
        with self._lock:
            self.n_requests += lat.shape[0]
            self.request_ms.extend(lat)
            self.n_slo_miss += miss

    def record_shed(self, n: int = 1) -> None:
        with self._lock:
            self.n_shed += n

    @property
    def readings_per_s(self) -> float:
        """Readings per second of dispatch (`busy_s`), not throughput."""
        return self.n_readings / self.busy_s if self.busy_s > 0 else 0.0

    def percentile_ms(self, q: float) -> float:
        return self.batch_ms.percentile(q)

    def request_percentile_ms(self, q: float) -> float:
        return self.request_ms.percentile(q)

    def summary(self) -> dict:
        with self._lock:
            return {
                "n_readings": self.n_readings,
                "n_batches": self.n_batches,
                "busy_s": round(self.busy_s, 6),
                "readings_per_s": round(self.readings_per_s, 1),
                "p50_ms": round(self.batch_ms.percentile(50), 4),
                "p99_ms": round(self.batch_ms.percentile(99), 4),
                "n_requests": self.n_requests,
                "req_p50_ms": round(self.request_ms.percentile(50), 4),
                "req_p99_ms": round(self.request_ms.percentile(99), 4),
                "n_slo_miss": self.n_slo_miss,
                "n_shed": self.n_shed,
                "window": self.window,
            }


@dataclass
class SensorRequest:
    uid: int
    readings: np.ndarray             # (F,) raw sensor values
    label: int | None = None
    latency_ms: float | None = None  # submit -> label
    deadline_ms: float | None = None  # latency budget (SLO), if any
    _t_submit: float = 0.0

    @property
    def slo_miss(self) -> bool:
        return (self.deadline_ms is not None and self.latency_ms is not None
                and self.latency_ms > self.deadline_ms)


def attach_labels(group: list, labels: np.ndarray
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Set each dispatched request's label and submit -> label latency.

    Returns the latencies and the deadlines (NaN where a request has
    none), in ms, for `ServeStats.record_requests`."""
    t_done = time.perf_counter()
    lat = np.empty(len(group))
    dl = np.empty(len(group))
    for i, (r, lbl) in enumerate(zip(group, labels)):
        r.label = int(lbl)
        r.latency_ms = lat[i] = (t_done - r._t_submit) * 1e3
        dl[i] = np.nan if r.deadline_ms is None else r.deadline_ms
    return lat, dl


class CircuitServingEngine:
    """Batched request->label serving over one compiled classifier."""

    def __init__(self, program: CircuitProgram, max_batch: int = 1024,
                 stats_window: int = STATS_WINDOW):
        if program.n_classes is None:
            raise ValueError("engine needs a classifier program "
                             "(CircuitProgram.from_classifier)")
        if max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.program = program
        self.max_batch = max_batch
        self.stats = ServeStats(window=stats_window)
        self._queue: list[SensorRequest] = []
        self._next_uid = 0
        self._lock = threading.Lock()

    @property
    def n_features(self) -> int:
        return self.program.ir.n_inputs

    def warmup(self) -> float:
        """Trigger jit compilation of the fixed batch shape (not counted).

        Returns the wall time of one *warm* dispatch in seconds — callers
        (the fleet scheduler) use it to seed their dispatch-interval
        estimate.
        """
        dummy = np.zeros((self.max_batch, self.n_features), dtype=np.float64)
        for _ in range(2):       # first call compiles; second is the measure
            t0 = time.perf_counter()
            if self.program.thresholds is not None:
                self.program.predict(dummy)
            else:
                self.program.predict_bits(dummy.astype(np.uint8))
            dt = time.perf_counter() - t0
        return dt

    # -- request-queue path -------------------------------------------------
    def submit(self, readings: np.ndarray,
               deadline_ms: float | None = None) -> SensorRequest:
        readings = np.asarray(readings, dtype=np.float64).reshape(-1)
        if readings.shape[0] != self.n_features:
            raise ValueError(f"expected {self.n_features} features, "
                             f"got {readings.shape[0]}")
        with self._lock:
            req = SensorRequest(self._next_uid, readings,
                                deadline_ms=deadline_ms,
                                _t_submit=time.perf_counter())
            self._next_uid += 1
            self._queue.append(req)
        return req

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    def _pop_group(self) -> list[SensorRequest]:
        with self._lock:
            group = self._queue[: self.max_batch]
            del self._queue[: len(group)]
        return group

    def flush(self) -> list[SensorRequest]:
        """Drain the queue in arrival order; returns the completed requests.

        Each batch is popped atomically before dispatch, so requests that
        arrive while a dispatch is in flight — or a second flusher running
        concurrently — find the queue consistent: every request is
        dispatched exactly once and always completes with both `label` and
        `latency_ms` set (regression-pinned in tests/test_circuit_engine).
        """
        done: list[SensorRequest] = []
        while True:
            group = self._pop_group()
            if not group:
                break
            x = np.stack([r.readings for r in group])
            labels = self._dispatch(x)
            self.complete(group, labels)
            done.extend(group)
        return done

    def complete(self, group: list[SensorRequest],
                 labels: np.ndarray) -> None:
        """Attach labels + latency to dispatched requests (stats included)."""
        self.stats.record_requests(*attach_labels(group, labels))

    # -- bulk path ----------------------------------------------------------
    def classify_stream(self, x: np.ndarray) -> np.ndarray:
        """Classify `(S, F)` readings in max_batch chunks; returns `(S,)`."""
        x = np.asarray(x)
        if x.ndim != 2 or x.shape[1] != self.n_features:
            raise ValueError(f"expected (S, {self.n_features}) readings, "
                             f"got {x.shape}")
        out = np.empty(x.shape[0], dtype=np.int32)
        for s in range(0, x.shape[0], self.max_batch):
            chunk = x[s: s + self.max_batch]
            out[s: s + chunk.shape[0]] = self._dispatch(chunk)
        return out

    def classify_batch(self, x: np.ndarray) -> np.ndarray:
        """One `(B <= max_batch, F)` batch -> labels, padded to the jit shape.

        The fleet dispatch path: the scheduler forms the batch, the engine
        executes it.
        """
        x = np.asarray(x)
        if x.ndim != 2 or x.shape[1] != self.n_features:
            raise ValueError(f"expected (B, {self.n_features}) readings, "
                             f"got {x.shape}")
        if x.shape[0] > self.max_batch:
            raise ValueError(f"batch of {x.shape[0]} exceeds max_batch "
                             f"{self.max_batch}")
        return self._dispatch(x)

    def prepare_packed_batch(self, x: np.ndarray) -> tuple[np.ndarray, int]:
        """One `(B <= max_batch, F)` batch -> packed uint32 word plane.

        The megakernel half of `classify_batch`: validate, binarize
        through the program's thresholds (or take raw bits when there are
        none), zero-pad to the compiled `max_batch` shape, and bit-pack to
        the `(F, max_batch/32)` plane a fused fleet launch consumes.
        Returns `(words32, B)`; the caller slices the decoded labels back
        to `B` rows (pad rows decode through the same circuit and are
        discarded).
        """
        x = np.asarray(x)
        if x.ndim != 2 or x.shape[1] != self.n_features:
            raise ValueError(f"expected (B, {self.n_features}) readings, "
                             f"got {x.shape}")
        B = x.shape[0]
        if B > self.max_batch:
            raise ValueError(f"batch of {B} exceeds max_batch "
                             f"{self.max_batch}")
        return self.program.pack_input_bits(self._binarize_padded(x)), B

    def _binarize_padded(self, x: np.ndarray) -> np.ndarray:
        """Readings -> 0/1 bits through the program's thresholds (or raw
        bits when it has none), zero-padded to the compiled `max_batch`
        rows; pad rows decode through the circuit and are dropped."""
        with obs.span("dispatch.binarize"):
            xbin = (self.program.binarize(x)
                    if self.program.thresholds is not None
                    else np.asarray(x, dtype=np.uint8))
            B = xbin.shape[0]
            if B < self.max_batch:
                pad = np.zeros((self.max_batch - B, xbin.shape[1]),
                               dtype=xbin.dtype)
                xbin = np.concatenate([xbin, pad], axis=0)
        return xbin

    def _dispatch(self, x: np.ndarray) -> np.ndarray:
        """One padded fixed-shape batch through the program (timed)."""
        B = x.shape[0]
        t0 = time.perf_counter()
        labels = self.program.predict_bits(self._binarize_padded(x))
        dt = time.perf_counter() - t0
        self.stats.record(B, dt)
        return labels[:B]
