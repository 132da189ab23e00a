"""The serving driver: seeded weights -> snapshot -> ``ServeSession``, the
object ``task = serve`` builds, then requests from one generator in this
process (nothing is forked: a chip belongs to one process).

``loop = open``    independent callers: requests are due on a schedule
    fixed before the window — Poisson gaps at ``rate_per_s``, rows per
    request from ``rows_per_request`` — and are sent when due whatever
    the server does. Latency is counted from the time a request was DUE,
    so a stall charges every request it delays; how late the generator
    itself ran is reported beside it. The set of gaps and sizes is the
    same for every ``--seed``; the seed orders them and picks the rows.
``loop = closed``  ``clients`` callers that each wait for their reply
    before sending again (after serve/server.py's ``run_closed_loop``),
    until the window ends; latency is counted from the send.

``sweep(run)`` steps an open loop's rate up inside one process and prints
one row per rate; the knee is the highest rate whose backlog did not
grow, and a steady cell runs at four fifths of it (README.md).
"""

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from harness import BenchFailure, Run, percentile, trace_options

# Two answers to one row from different micro-batches may differ by the
# rounding of a bf16 forward pass reduced in another order by another
# bucket's program: a few bf16 ulps (2^-8 each) of the largest
# probability. Another row's answer differs by far more; rescore()
# proves that on the sample itself before it trusts the comparison.
RESCORE_TOL = 4 * 2.0 ** -8


def schedule(traffic: Dict[str, Any], seed: int, seconds: float,
             rate: Optional[float] = None
             ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(due_s, rows, pool_offset)`` of an open loop's requests. Gaps
    (exponential, mean 1/rate) and sizes come from a fixed generator, so
    every seed offers the same work; the seed shuffles their order and
    draws the pool offsets."""
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng(0)
    gaps = fixed.exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()            # the last request is due at the end
    sizes = sorted(int(k) for k in traffic["rows_per_request"])
    probs = [float(traffic["rows_per_request"][str(k)]) for k in sizes]
    rows = fixed.choice(sizes, n, p=np.asarray(probs) / sum(probs))
    rng = np.random.default_rng(seed)
    rng.shuffle(gaps)
    rng.shuffle(rows)
    offsets = rng.integers(0, int(traffic["pool_rows"]), n)
    return np.cumsum(gaps) - gaps[0], rows, offsets


class Served:
    """The session under test, its pool of rows and its records."""

    def __init__(self, r: Run) -> None:
        from cxxnet_tpu.monitor import MemorySink, Monitor
        from cxxnet_tpu.nnet.trainer import NetTrainer
        from cxxnet_tpu.serve.server import ServeSession
        from cxxnet_tpu.utils.config import parse_config
        c = r.config
        with open(os.path.join(c["_dir"], c["netconfig"])) as f:
            net = parse_config(f.read()) + [("dtype", c["dtype"]),
                                            ("silent", "1")]
        trainer = NetTrainer(net + [("seed", str(r.seed32()))])
        trainer.init_model()
        snapshot = os.path.join(r.out_dir, "seeded.model.npz")
        trainer.save_model(snapshot)
        del trainer
        self.sink = MemorySink()
        self.session = ServeSession(
            net + [(k, str(v)) for k, v in r.traffic["serve"].items()],
            model_path=snapshot, monitor=Monitor(self.sink))
        shape = self.session.engine._inst_shape()
        rng = np.random.default_rng(r.seed32())
        # mean-subtracted pixels, the iterator's output range
        self.pool = rng.uniform(-128.0, 128.0, (int(r.traffic["pool_rows"]),)
                                + tuple(shape)).astype(np.float32)
        self.nclass = int(c["nclass"])

    def rows(self, offset: int, n: int) -> np.ndarray:
        return np.take(self.pool, range(offset, offset + n), axis=0,
                       mode="wrap")

    def warm(self, sizes: Sequence[int]) -> None:
        """The session warmed its programs itself; this walks the path
        from submit to a resolved Future once per request size."""
        for n in sizes:
            for _ in range(3):
                self.session.predict(self.rows(0, int(n)))


class Tracer:
    """A trace of ``seconds`` seconds, begun one second into the window
    from the generator's own loop."""

    def __init__(self, r: Run, t0: float) -> None:
        self.r, self.on = r, False
        self.begin = t0 + min(1.0, r.seconds / 4)
        self.end = self.begin + float(r.traffic["trace_seconds"])
        r.trace_dir = os.path.join(r.out_dir, "trace")

    def poll(self, now: float, last: bool = False) -> None:
        import jax
        if not self.on and not self.r.trace_span_s and now >= self.begin:
            jax.profiler.start_trace(
                self.r.trace_dir,
                profiler_options=trace_options(self.r.traffic))
            self.t_on = time.time()
            self.on = True
        elif self.on and (now >= self.end or last):
            self.r.trace_span_s = time.time() - self.t_on
            jax.profiler.stop_trace()   # its own work is not in the span
            self.on = False


def open_loop(s: Served, due: np.ndarray, rows: np.ndarray,
              offsets: np.ndarray, tracer: Optional[Tracer] = None):
    """Send each request when it is due. Returns ``(t0, sent_at, done_at,
    results)``: wall-clock seconds, NaN where a request was refused or
    never resolved; ``results[i]`` is the answer or the exception."""
    import jax
    n = len(due)
    sent_at = np.full(n, np.nan)
    done_at = np.full(n, np.nan)
    results: List[Any] = [None] * n

    def stamp(i: int):
        def done(fut) -> None:
            done_at[i] = time.time()
            exc = fut.exception()
            results[i] = exc if exc is not None else fut.result()
        return done

    t0 = time.time()
    for i in range(n):
        wait = t0 + due[i] - time.time()
        if wait > 0:
            time.sleep(wait)
        if tracer is not None:
            tracer.poll(time.time())
        payload = s.rows(int(offsets[i]), int(rows[i]))
        sent_at[i] = time.time()
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                s.session.submit(payload).add_done_callback(stamp(i))
        except Exception as e:   # refused (queue full, closed): it failed
            results[i] = e
    return t0, sent_at, done_at, results


def wait_resolved(results: List[Any], grace_s: float) -> None:
    deadline = time.time() + grace_s
    while time.time() < deadline:
        if all(r is not None for r in results):
            return
        time.sleep(0.01)


def check_answers(r: Run, s: Served, results: List[Any]) -> None:
    bad = 0
    for out in results:
        if isinstance(out, np.ndarray):
            o = np.asarray(out, np.float32)
            ok = (o.ndim == 2 and o.shape[1] == s.nclass
                  and np.isfinite(o).all()
                  and np.abs(o.sum(axis=1) - 1.0).max() <= 1e-2)
            bad += 0 if ok else 1
    r.check(bad == 0, "%d answer(s) are not rows of %d finite "
            "probabilities summing to 1 within 1e-2" % (bad, s.nclass))


def rescore(r: Run, s: Served, rows: np.ndarray, offsets: np.ndarray,
            results: List[Any]) -> None:
    """A seeded sample of the window's requests, scored again one at a
    time through the same session: padding, bucket choice and batch
    composition may not change an answer."""
    ok = [i for i, out in enumerate(results) if isinstance(out, np.ndarray)]
    rng = np.random.default_rng(r.seed32())
    sample = rng.choice(ok, min(int(r.traffic["rescore_sample"]), len(ok)),
                        replace=False) if ok else []
    worst, alone = 0.0, {}
    for i in sample:
        again = np.asarray(s.session.predict(
            s.rows(int(offsets[i]), int(rows[i]))), np.float32)
        alone[int(offsets[i])] = again[0]
        was = np.asarray(results[i], np.float32)
        worst = max(worst, float(np.abs(again - was).max()
                                 / np.abs(again).max()))
    firsts = list(alone.values())
    power = min((float(np.abs(a - b).max() / np.abs(a).max())
                 for k, a in enumerate(firsts) for b in firsts[k + 1:]),
                default=float("inf"))
    r.notes["rescore"] = {"sample": len(sample), "worst_rel": worst,
                          "tol": RESCORE_TOL, "closest_other_row": power}
    r.check(len(sample) > 0, "no answered request to score again")
    r.check(worst <= RESCORE_TOL,
            "an answer changed by %.3g of its largest probability when "
            "scored alone (tolerance %.3g)" % (worst, RESCORE_TOL))
    r.check(power > 4 * RESCORE_TOL,
            "two different rows answer within %.3g of each other: the "
            "comparison could not tell a neighbour's row" % power)


def latencies_ms(r: Run, start_at: np.ndarray, done_at: np.ndarray,
                 results: List[Any]) -> List[float]:
    """Per request, resolve time minus ``start_at`` in ms; a request that
    failed or never resolved counts as the worst latency there is."""
    failed = np.array([not isinstance(out, np.ndarray) for out in results])
    lat = (done_at - start_at) * 1e3
    worst = max([(r.seconds + float(r.traffic["resolve_grace_s"])) * 1e3]
                + [float(v) for v in lat[~failed]])
    lat[failed] = worst
    r.attempted, r.failed = len(results), int(failed.sum())
    r.check(r.failed == 0, "%d of %d requests failed, were refused or did "
            "not resolve" % (r.failed, r.attempted))
    return [float(v) for v in lat]


def finish(r: Run, s: Served, lat: List[float]) -> None:
    from cxxnet_tpu.monitor.schema import validate_records
    summary = s.session.close()
    validate_records(s.sink.records)
    r.records = list(s.sink.records)
    r.check(summary["compile_events"] == 0,
            "%d compile event(s) after warm-up" % summary["compile_events"])
    r.end_to_end["serve_p95_ms"] = percentile(lat, 0.95)
    r.end_to_end["serve_p50_ms"] = percentile(lat, 0.50)
    r.notes["summary"] = {k: summary[k] for k in (
        "requests", "rows", "batches", "rejected", "timeouts", "errors",
        "fill_rate", "pad_fraction", "compile_events")}


def run(r: Run) -> None:
    s = Served(r)
    s.warm(sorted(int(k) for k in r.traffic["rows_per_request"]))
    tracer = Tracer(r, time.time()) if r.trace else None
    if r.traffic["loop"] == "open":
        due, rows, offsets = schedule(r.traffic, r.seed32(), r.seconds)
        t0, sent_at, done_at, results = open_loop(s, due, rows, offsets,
                                                  tracer)
        start_at = t0 + due
        r.samples["gen_late_ms"] = [float(v) for v in
                                    (sent_at - start_at) * 1e3]
    elif r.traffic["loop"] == "closed":
        t0, start_at, done_at, results, rows, offsets = closed_loop(
            r, s, tracer)
    else:
        raise BenchFailure("serve traffic: loop = %r" % r.traffic["loop"])
    r.window = (t0, t0 + r.seconds)
    wait_resolved(results, float(r.traffic["resolve_grace_s"]))
    if tracer is not None:
        tracer.poll(time.time(), last=True)
        if not r.trace_span_s:
            raise BenchFailure("the window ended before the trace began")
    lat = latencies_ms(r, start_at, done_at, results)
    check_answers(r, s, results)
    rescore(r, s, rows, offsets, results)
    done_rows = sum(int(n) for n, out in zip(rows, results)
                    if isinstance(out, np.ndarray))
    r.end_to_end["serve_rows_per_s"] = done_rows / r.seconds
    finish(r, s, lat)


def closed_loop(r: Run, s: Served, tracer: Optional[Tracer]):
    """``clients`` callers, each waiting for its reply before it sends
    again, until the window ends; requests in flight then may finish."""
    (size,) = [int(k) for k in r.traffic["rows_per_request"]]
    per: List[List[Tuple[float, float, Any, int]]] = [
        [] for _ in range(int(r.traffic["clients"]))]
    t0 = time.time()

    def client(ci: int) -> None:
        rng = np.random.default_rng([r.seed32(), ci])
        while time.time() - t0 < r.seconds:
            offset = int(rng.integers(0, s.pool.shape[0]))
            sent = time.time()
            try:
                out = s.session.predict(s.rows(offset, size))
            except Exception as e:          # busy, timeout, error: it failed
                out = e
            per[ci].append((sent, time.time(), out, offset))

    threads = [threading.Thread(target=client, args=(i,),
                                name="bench-client-%d" % i)
               for i in range(len(per))]
    for th in threads:
        th.start()
    while any(th.is_alive() for th in threads):
        if tracer is not None:
            tracer.poll(time.time())
        time.sleep(0.05)
    for th in threads:
        th.join()
    flat = [x for c in per for x in c]
    sent = np.array([x[0] for x in flat])
    done = np.array([x[1] for x in flat])
    return (t0, sent, done, [x[2] for x in flat],
            np.full(len(flat), size), np.array([x[3] for x in flat]))


def sweep(r: Run) -> None:
    """One process, one session, the offered rate stepped up: a row per
    rate. The backlog grew where more requests were unresolved at the
    step's end than at its middle, by more than a hundredth of the
    offer."""
    s = Served(r)
    s.warm(sorted(int(k) for k in r.traffic["rows_per_request"]))
    step = float(r.traffic["sweep"]["step_seconds"])
    for rate in r.traffic["sweep"]["rates_per_s"]:
        due, rows, offsets = schedule(r.traffic, r.seed32(), step, rate)
        t0, sent_at, done_at, results = open_loop(s, due, rows, offsets)
        t_end = time.time()

        def unresolved(at: float) -> int:
            return int(((sent_at <= at) & ~(done_at <= at)).sum())

        mid, end = unresolved(t0 + step / 2), unresolved(t_end)
        wait_resolved(results, float(r.traffic["resolve_grace_s"]))
        ok = np.array([isinstance(out, np.ndarray) for out in results])
        lat = ((done_at - (t0 + due)) * 1e3)[ok]
        row = {"phase": "sweep", "offered_per_s": rate, "requests": len(due),
               "answered": int(ok.sum()),
               "rows_per_s": float(rows[ok].sum() / step),
               "unresolved_mid": mid, "unresolved_end": end,
               "backlog_grew": bool(end > mid + max(5, 0.01 * len(due))
                                    or not ok.all()),
               "gen_late_p95_ms": percentile(
                   [float(v) for v in (sent_at - (t0 + due)) * 1e3], 0.95)}
        if ok.any():
            row["p50_ms"] = percentile([float(v) for v in lat], 0.5)
            row["p95_ms"] = percentile([float(v) for v in lat], 0.95)
        print(json.dumps(row), flush=True)
        if row["backlog_grew"] and not ok.all():
            break                           # refusing already: far past the knee
    s.session.close()
    print(json.dumps({"phase": "sweep_end",
                      "memory_peak_bytes": max(
                          int((d.memory_stats() or {}).get(
                              "peak_bytes_in_use", 0))
                          for d in r.devices)}), flush=True)
