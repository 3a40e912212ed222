"""The traffic drivers: one per kind of mix, each read from a data file.

A mix (``bench/traffic/<mix>.json``) names its ``kind`` and the
parameters that kind reads:

- ``batch``: a closed loop of facade searches, ``batch`` queries at a
  time, one batch in flight, ``k`` answers each, over a pool of
  ``pool_batches`` batches taken in turn from a start drawn from the
  seed.
- ``open``: single requests through ``repro.serve.RequestScheduler``,
  sent on a schedule whether or not earlier ones are answered
  (``schedule``: Poisson at ``rate_per_s``, ``k`` answers each, every
  query new; the ``serve_config`` keys go to ``ServeConfig``).  Each request is timed
  from its due time to its delivery.
- ``cp``: a closed loop of whole closest-pair jobs, ``index.cp_search(k)``.

Each driver has the same four steps: ``setup`` (data on the device,
build, warm-up of the cell's own shapes), ``window`` (the measured
traffic; the window closes at the end of the last unit of work that
started inside it), ``release`` (frees the program's state) and
``check`` (the reference, outside the window).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import os
import sys
import time

import numpy as np

import data as bdata
import reference

clock = time.perf_counter


@contextlib.contextmanager
def step(name: str):
    """Log how long one part of set-up takes (standard error)."""
    t0 = clock()
    yield
    print(f"[bench] {name}_s={clock() - t0:.3f}", file=sys.stderr,
          flush=True)


@contextlib.contextmanager
def gc_pauses(out: list):
    """Append (generation, seconds) of each garbage collection inside the
    block (read for the log: a stall in the window is named by it or not)."""
    began = [0.0]

    def note(phase, info):
        if phase == "start":
            began[0] = clock()
        else:
            out.append((info["generation"], clock() - began[0]))

    gc.callbacks.append(note)
    try:
        yield
    finally:
        gc.callbacks.remove(note)


def annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def index_config(cfg: dict, seed: int, options: dict | None = None):
    from repro.index import IndexConfig

    ix = dict(cfg["index"])
    opts = {**ix.pop("options", {}), **(options or {})}
    # the projection family's key takes a 32-bit seed
    return IndexConfig(**ix, seed=seed % (2**31 - 1),
                       options=opts)


def host_data(cfg: dict, seed: int) -> np.ndarray:
    """The point set, made on the device and copied once to the host
    (the facade builds from host rows)."""
    x = bdata.dataset(cfg, seed)
    host = np.asarray(x)
    del x
    return host


def device_rows(x_host: np.ndarray):
    import jax.numpy as jnp

    return jnp.asarray(x_host)


@dataclasses.dataclass
class Window:
    start: float
    end: float
    attempted: int
    failed: int
    e2e: dict  # end-to-end metrics measured by the host clock
    counters: dict  # work counts for the per-layer readers
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (failed requests enter as inf)."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return math.inf
    return float(v[max(math.ceil(q * len(v)) - 1, 0)])


# ---------------------------------------------------------------------------
# batch: closed loop of facade searches


class Batch:
    def __init__(self, cell):
        self.cell = cell
        self.cfg, self.mix = cell.cfg, cell.traffic
        self.B, self.k = int(self.mix["batch"]), int(self.mix["k"])

    def setup(self):
        from repro.index import build_index

        cfg, seed = self.cfg, self.cell.seed
        with step("data"):
            self.x_host = host_data(cfg, seed)
            nb = int(self.mix["pool_batches"])
            pool = np.asarray(bdata.queries(cfg, seed, (nb + 1) * self.B))
            self.warm_q, self.pool = pool[:self.B], pool[self.B:]
        with step("build"):
            self.index = build_index(self.x_host, index_config(
                cfg, seed, self.cell.options))
        with step("warm"):
            for _ in range(2):  # the second call must find everything warm
                self.index.search(self.warm_q, k=self.k)

    def window(self, seconds: float) -> Window:
        B, k, nb = self.B, self.k, len(self.pool) // self.B
        first = int(np.random.default_rng(self.cell.seed).integers(nb))
        rows, ids, dists, times, cpu, calls = [], [], [], [], [], 0
        rerouted, pauses = [], []
        t0 = clock()
        end = t0
        with annotate("bench.window"), gc_pauses(pauses):
            while calls == 0 or end - t0 < seconds:
                b = (first + calls) % nb  # the pool's batches, rotated
                start, c0 = clock(), os.times()
                with annotate("bench.search"):
                    res = self.index.search(self.pool[b * B:(b + 1) * B], k=k)
                end, c1 = clock(), os.times()
                times.append(end - start)
                cpu.append(c1.user - c0.user + c1.system - c0.system)
                rows.append(b)
                ids.append(res.indices)
                dists.append(res.distances)
                calls += 1
                # select's exact-sort fallback (a tie cluster wider than
                # its buffer) reports the budget for every row
                cnt = getattr(self.index, "last_select_counts", None)
                T = getattr(self.index, "last_select_budget", None)
                rerouted.append(bool(cnt is not None and (cnt == T).all()))
        self.rows = np.concatenate([np.arange(r * B, (r + 1) * B)
                                    for r in rows])
        self.ids, self.dists = np.concatenate(ids), np.concatenate(dists)
        n = self.x_host.shape[0]
        from_T = getattr(self.index, "last_select_budget", None)
        counters = {"ann": {"calls": calls, "B": B, "k": k, "n": n,
                            "d": self.x_host.shape[1],
                            "m": int(self.cfg["index"]["m"]),
                            "T": int(from_T) if from_T else None,
                            "rerouted": sum(rerouted)}}
        fast = min(times)
        extra = {"batch_s_min": fast, "batch_s_max": max(times),
                 "rerouted_batches": sum(rerouted),
                 # [call, pool batch, seconds, host CPU seconds, rerouted]
                 # of each batch well over the fastest, and the
                 # collector's pauses: what a stall was
                 "slow_batches": [[i, rows[i], times[i], cpu[i], rerouted[i]]
                                  for i in range(calls)
                                  if times[i] > 1.2 * fast],
                 "cpu_s_median": float(np.median(cpu)),
                 "gc_s": sum(p for _, p in pauses),
                 "gc_max_s": max((p for _, p in pauses), default=0.0)}
        return Window(t0, end, calls * B, 0,
                      {"ann_qps": calls * B / (end - t0)}, counters, extra)

    def release(self):
        del self.index
        gc.collect()  # the program's arrays leave the chip

    def check(self, control: bool = False):
        from check import knn_numbers

        ids, dists = self.ids, self.dists
        uniq, inv = np.unique(self.rows, return_inverse=True)
        x_dev = device_rows(self.x_host)
        exact, _ = reference.knn(x_dev, self.x_host, self.pool[uniq], self.k)
        if control:
            cid, cd = reference.knn(x_dev, self.x_host, self.pool[uniq],
                                    self.k, "high")
            ids, dists = cid[inv], cd[inv]
        del x_dev
        nums = knn_numbers(ids, dists, self.x_host, self.pool[self.rows],
                           exact[inv])
        return nums, {"recall_at_10": 1.0 - nums["recall_miss"]}


# ---------------------------------------------------------------------------
# open: single requests through the scheduler, on a schedule


def schedule(mix: dict, seconds: float, seed: int):
    """The requests of one window: (send times in [0, seconds), pool row)
    of each.

    One trace of exponential gaps at the mix's mean rate, every query
    new, is replayed by every run, rotated to start at a request drawn
    from the run's seed: every seed gets the same gaps in another
    order.  (A fresh order per seed made the tail of this open-loop
    queue a property of where the bursts fell: runs of one cell
    disagreed by more than any change worth finding.)
    """
    rate = float(mix["rate_per_s"])
    count = max(int(round(rate * seconds)), 1)
    u = (np.arange(count) + 0.5) / count
    gaps = np.random.default_rng(0).permutation(
        -np.log1p(-u) / rate)  # exponential quantiles
    shift = -int(np.random.default_rng(seed).integers(count))
    gaps, rows = np.roll(gaps, shift), np.roll(np.arange(count), shift)
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return t, rows


def latencies(due, delivered, ok) -> np.ndarray:
    """Seconds from each request's due time to its delivery; a request
    that failed, was shed or degraded, or never came counts as inf."""
    due = np.asarray(due, np.float64)
    out = np.asarray(delivered, np.float64) - due
    return np.where(np.asarray(ok, bool) & np.isfinite(out), out, np.inf)


class Open:
    def __init__(self, cell):
        self.cell = cell
        self.cfg, self.mix = cell.cfg, cell.traffic
        self.k = int(self.mix["k"])

    def setup(self):
        from repro.serve import RequestScheduler, RetrievalStep, ServeConfig

        cfg, mix, seed = self.cfg, self.mix, self.cell.seed
        with step("data"):
            self.x_host = host_data(cfg, seed)
        with step("build"):
            self.step = RetrievalStep(
                self.x_host, np.arange(len(self.x_host), dtype=np.int32),
                k=self.k, index_config=index_config(
                    cfg, seed, self.cell.options))
            sc = ServeConfig(**mix.get("serve_config", {}))
            self.sched = RequestScheduler(self.step, config=sc)
        kp = self.sched.palette.k_pad(self.k)
        bs = sorted({self.sched.palette.b_pad(b)
                     for b in range(1, sc.b_max + 1)})
        self.warm_q = np.asarray(bdata.queries(cfg, seed, sc.b_max + 1))
        rows = self.warm_q[:sc.b_max]
        with step("warm"):
            for b in bs:  # the flush shapes of this traffic, no others
                for _ in range(2):
                    self.step.index.search(rows[:b], k=kp)
            # one request through the scheduler: its host path, and a
            # service estimate from this size rather than none
            self.sched.submit(self.warm_q[-1], k=self.k).result()

    def window(self, seconds: float, rate: float | None = None,
               stream: int = 2) -> Window:
        mix = dict(self.mix)
        if rate is not None:
            mix["rate_per_s"] = rate
        seed = self.cell.seed
        t_due, qrow = schedule(mix, seconds, seed)
        count = len(t_due)
        npool = int(qrow.max()) + 1
        self.pool = np.asarray(bdata.queries(self.cfg, seed, npool, stream))
        sched = self.sched
        base = sched.snapshot()
        tickets = [None] * count
        delivered = np.full(count, np.inf)
        late = np.zeros(count)
        pending: list[int] = []
        i = 0
        t0 = clock()
        with annotate("bench.window"):
            while i < count or pending:
                now = clock() - t0
                if i < count and t_due[i] <= now:
                    with annotate("bench.submit"):
                        while i < count and t_due[i] <= now:
                            late[i] = now - t_due[i]
                            tickets[i] = sched.submit(self.pool[qrow[i]],
                                                      k=self.k)
                            pending.append(i)
                            i += 1
                with annotate("bench.pump"):
                    sched.pump()
                now = clock() - t0
                still = []
                for j in pending:
                    if tickets[j].done:
                        delivered[j] = now
                    else:
                        still.append(j)
                pending = still
                if i < count and not pending:
                    wait = t_due[i] - (clock() - t0)
                    if wait > 0:
                        with annotate("bench.idle"):
                            time.sleep(min(wait, 0.002))
                elif pending:
                    time.sleep(0.0002)
        end = t0 + float(np.max(delivered[np.isfinite(delivered)],
                                initial=0.0))
        resp = [t.result() for t in tickets]
        ok = np.array([r.ok and not r.degraded for r in resp])
        lat = latencies(t_due, delivered, ok)
        snap = sched.snapshot()
        real = (sum(b.real_slots for b in snap.buckets)
                - sum(b.real_slots for b in base.buckets))
        padded = (sum(b.padded_slots for b in snap.buckets)
                  - sum(b.padded_slots for b in base.buckets))
        self.resp, self.qrow, self.ok = resp, qrow, ok
        counters = {"serve": {"real_slots": real, "padded_slots": padded,
                              "cache_hits": snap.cache_hits
                              - base.cache_hits}}
        first = t_due < seconds / 2  # a backlog that grows shows here
        extra = {"requests": count, "p50_ms": nearest_rank(lat, 0.5) * 1e3,
                 "p95_first_half_ms": nearest_rank(lat[first], 0.95) * 1e3,
                 "p95_second_half_ms": nearest_rank(lat[~first], 0.95) * 1e3,
                 "generator_late_max_ms": float(late.max()) * 1e3,
                 "completed_per_s": float(ok.sum()) / max(end - t0, 1e-9),
                 "offered_per_s": count / seconds}
        return Window(t0, end, count, int((~ok).sum()),
                      {"serve_p95_ms": nearest_rank(lat, 0.95) * 1e3},
                      counters, extra)

    def release(self):
        del self.sched, self.step
        gc.collect()  # the program's arrays leave the chip

    def check(self, control: bool = False):
        from check import knn_numbers

        k = self.k
        # the answers delivered; a request that failed, was shed or
        # degraded is counted in ``failed`` and its latency is inf
        ids = np.full((len(self.resp), k), -1, np.int64)
        dists = np.full((len(self.resp), k), np.inf)
        for j, r in enumerate(self.resp):
            if r.ok and not r.degraded:
                ids[j] = r.result.indices[0, :k]
                dists[j] = r.result.distances[0, :k]
        judged = self.ok | control
        if not judged.any():  # nothing delivered: every request is bad
            judged[:] = True
        uniq, inv = np.unique(self.qrow, return_inverse=True)
        x_dev = device_rows(self.x_host)
        exact, _ = reference.knn(x_dev, self.x_host, self.pool[uniq], k)
        if control:
            cid, cd = reference.knn(x_dev, self.x_host, self.pool[uniq], k,
                                    "high")
            ids, dists = cid[inv], cd[inv]
        del x_dev
        nums = knn_numbers(ids[judged], dists[judged], self.x_host,
                           self.pool[self.qrow[judged]], exact[inv[judged]])
        return nums, {"recall_at_10": 1.0 - nums["recall_miss"]}


# ---------------------------------------------------------------------------
# cp: closed loop of whole closest-pair jobs


class Cp:
    def __init__(self, cell):
        self.cell = cell
        self.cfg, self.k = cell.cfg, int(cell.traffic["k"])

    def setup(self):
        from repro.index import build_index

        with step("data"):
            self.x_host = host_data(self.cfg, self.cell.seed)
        with step("build"):
            self.index = build_index(self.x_host, index_config(
                self.cfg, self.cell.seed, self.cell.options))
        with step("warm"):
            self.index.cp_search(self.k)

    def window(self, seconds: float) -> Window:
        jobs, verified, times, pauses = [], 0, [], []
        t0 = clock()
        end = t0
        with annotate("bench.window"), gc_pauses(pauses):
            while not jobs or end - t0 < seconds:
                start = clock()
                with annotate("bench.cp_search"):
                    res = self.index.cp_search(self.k)
                end = clock()
                times.append(end - start)
                jobs.append((res.pairs, res.distances))
                verified += int(res.stats.pairs_verified)
        self.jobs = jobs
        n, d = self.x_host.shape
        counters = {"cp": {"jobs": len(jobs), "n": n, "d": d, "k": self.k,
                           "pairs_verified": verified}}
        extra = {"job_s_min": min(times), "job_s_max": max(times),
                 "gc_s": sum(p for _, p in pauses)}
        return Window(t0, end, len(jobs), 0,
                      {"cp_job_s": (end - t0) / len(jobs)}, counters, extra)

    def release(self):
        del self.index
        gc.collect()  # the program's arrays leave the chip

    def check(self, control: bool = False):
        from check import cp_numbers

        x_dev = device_rows(self.x_host)
        exact, _ = reference.closest_pairs(x_dev, self.x_host, self.k)
        jobs = self.jobs
        if control:
            cp, cd = reference.closest_pairs(x_dev, self.x_host, self.k,
                                             "high")
            jobs = [(cp, cd)] * len(jobs)
        del x_dev
        return cp_numbers(jobs, self.x_host, exact), {}


KINDS = {"batch": Batch, "open": Open, "cp": Cp}
