"""Open-loop risk service: independent callers at a fixed rate.

Set-up makes an Appendix-C cohort from ``--seed``, splits it into a
training part and a pool of held-out request rows, and makes the
served coefficients from the seed by the configuration's rule (the
model's quality does not change the work of scoring). The program turns
them into an artifact (``serving.fit_survival_model``, Breslow baseline
on a grid), rolls it out through ``ModelRegistry`` (which warms every
batch bucket) and answers through ``RiskService``'s drain thread at its
default ``max_batch``; each request returns risk and median, no curve.

The window: the schedule of ``arrivals.py`` at the traffic file's rate.
The generator submits each request when it is due; a collector thread
waits for the responses in order. A request's latency runs from when it
was due, so a late generator shows as latency, and how late the
generator ran is printed. ``score_p99_ms`` is the 99th percentile over
every request due in the window; one that never comes counts as failed,
with the time waited for it as its latency.

The check: every served risk and median against the float64 reference
of the same coefficients and cohort (``reference/risk_median.py``).
"""
from __future__ import annotations

import json
import os
import queue
import tempfile
import threading
import time

import numpy as np

import arrivals
import datagen
import tracing
from reference import risk_median

WAIT_AFTER_CLOSE_S = 60.0
BLOCK = 4096     # rows the reference scores at a time


def coefficients(seed: int, beta_star: np.ndarray, rule: dict) -> np.ndarray:
    """Served coefficients: beta* scaled by (1 + a N(0,1)) on its
    support, plus b N(0,1) on every column, so no coefficient is zero or
    exactly representable in fewer bits."""
    rng = np.random.default_rng([abs(int(seed)), 3])
    p = beta_star.shape[0]
    beta = beta_star * (1.0 + rule["support_scale"]
                        * rng.standard_normal(p)) \
        + rule["dense_scale"] * rng.standard_normal(p)
    return beta.astype(np.float32)


class Driver:
    def __init__(self, config, traffic, seed, devices, log):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.devices = devices
        self.log = log
        self.counters = {}
        self.spans = []

    def setup(self):
        from repro.serving import ModelRegistry, RiskService
        from repro.serving import fit_survival_model

        c, sv = self.cfg, self.cfg["serve"]
        n, held = c["n"], sv["request_pool"]
        x, t, d, beta_star = datagen.appc(self.seed, n + held, c["p"],
                                          c["k"], c["rho"], c["s"],
                                          c["censor_scale"])
        self.train = (x[:n], t[:n], d[:n])
        self.pool = x[n:]
        self.beta = coefficients(self.seed, beta_star, sv["coefficients"])
        self.artifact = fit_survival_model(*self.train, self.beta,
                                           grid_size=sv["grid"])
        self.svc = RiskService(engine=None)
        self.registry = ModelRegistry(self.svc)
        self.registry.rollout("appc", self.artifact)
        engine = self.registry.engine()
        self.counters.update(engine_sparse=bool(engine.use_sparse),
                             features=int(engine.feature_dim))
        self.svc.start()
        # warm the drain thread and every bucket through the service
        for size in (1, 3, 7, 17, 64, 100):
            rids = [self.svc.submit(self.pool[i % len(self.pool)])
                    for i in range(size)]
            for r in rids:
                self.svc.wait(r, timeout=120.0)

    def window(self, seconds, capture):
        from repro.obs import trace as obs_trace
        from repro.serving import QueueFull, ScoreTimeout

        with tracing.annotate("bench.arrivals"):
            offs, rows = arrivals.schedule(self.traffic, seconds, self.seed,
                                           len(self.pool))
        n = len(offs)
        due = np.empty(n)
        late = np.empty(n)
        done = np.full(n, np.nan)
        answers = [None] * n
        rid_q: "queue.Queue" = queue.Queue()
        span_file = None
        if capture is not None:
            fd, span_file = tempfile.mkstemp(prefix="bench_spans_",
                                             suffix=".jsonl")
            os.close(fd)
            obs_trace.configure(span_file)
            capture.start()
        svc = self.svc
        give_up = [None]

        def collect():
            for k in range(n):
                item = rid_q.get()
                if item is None:
                    continue
                try:
                    left = max(give_up[0] - time.perf_counter(), 0.0)
                    resp = svc.wait(item, timeout=left)
                except ScoreTimeout:
                    continue
                done[k] = time.perf_counter()
                if resp.error is None:
                    answers[k] = (resp.risk, resp.median)

        collector = threading.Thread(target=collect, name="bench-collector",
                                     daemon=True)
        collector.start()
        with tracing.window(capture):
            t0 = time.perf_counter()
            due[:] = t0 + offs
            give_up[0] = t0 + seconds + WAIT_AFTER_CLOSE_S
            for k in range(n):
                wait = due[k] - time.perf_counter()
                if wait > 0:
                    with tracing.annotate("bench.next_arrival"):
                        time.sleep(wait)
                with tracing.annotate("bench.submit"):
                    late[k] = time.perf_counter() - due[k]
                    try:
                        rid_q.put(svc.submit(self.pool[rows[k]]))
                    except QueueFull:
                        rid_q.put(None)
            with tracing.annotate("bench.drain"):
                collector.join(max(give_up[0] - time.perf_counter(), 0.0)
                               + 1.0)
        if capture is not None:
            capture.stop()
            obs_trace.configure(None)
            with open(span_file) as f:
                self.spans = [json.loads(ln) for ln in f if ln.strip()]
            os.remove(span_file)
        t_end = time.perf_counter()
        lat = np.where(np.isnan(done), t_end - due, done - due)
        self.rows, self.answers = rows, answers
        self.counters.update(requests=n, latencies_s=lat,
                             dispatch_batches=[
                                 s["attrs"]["batch"] for s in self.spans
                                 if s.get("name") == "service.dispatch"],
                             queue_waits_s=[
                                 s["attrs"]["queue_wait_s"]
                                 for s in self.spans
                                 if s.get("name") == "service.request"])
        print(json.dumps({"generator": {
            "requests": n, "rate_per_s": self.traffic["rate_per_s"],
            "late_p50_ms": float(np.percentile(late, 50) * 1e3),
            "late_p99_ms": float(np.percentile(late, 99) * 1e3),
            "late_max_ms": float(late.max() * 1e3)},
            "engine_sparse": self.counters["engine_sparse"]}), flush=True)
        return {"score_p99_ms": float(np.percentile(lat, 99) * 1e3)}

    def release(self):
        self.svc.stop()
        self.registry = None

    def check(self):
        lim = self.cfg["limits"]["serve"]
        served = [k for k, a in enumerate(self.answers) if a is not None]
        failed = len(self.answers) - len(served)
        risk = np.asarray([self.answers[k][0] for k in served])
        median = np.asarray([self.answers[k][1] for k in served])
        nums = self.compare(np.asarray(served, int), risk, median)
        return {"correct": failed == 0,
                "attempted": len(self.answers), "failed": failed,
                "checks": {k: {"value": v, "limit": lim[k]}
                           for k, v in nums.items()}}

    def _reference(self):
        x, t, d = self.train
        grid = risk_median.time_grid(t, self.cfg["serve"]["grid"])
        return grid, risk_median.breslow_cumhaz(x, t, d, self.beta, grid)

    def compare(self, served, risk, median):
        """The numbers compared, for the requests ``served`` (indices
        into the window's schedule) and their risks and medians, in
        blocks of rows so that a long window fits."""
        band = self.cfg["limits"]["serve"]["median_band"]
        grid, h0 = self._reference()
        err, off = 0.0, 0
        for lo in range(0, len(served), BLOCK):
            sl = slice(lo, lo + BLOCK)
            risk_ref, curves = risk_median.scores(
                self.pool[self.rows[served[sl]]], self.beta, h0)
            err = max(err, float(np.max(np.abs(risk[sl] - risk_ref)
                                        / risk_ref)))
            off += int((~risk_median.medians_ok(median[sl], curves, grid,
                                                band)).sum())
        return {"risk_rel_err": err, "median_off": float(off)}

    def control(self):
        """The reference in the program's place at the precision below the
        engine's: x beta as three bfloat16 passes (``high``), the curve in
        float32, over the requests of the window."""
        grid, h0 = self._reference()
        h0 = h0.astype(np.float32)
        risk, median = [], []
        for lo in range(0, len(self.rows), BLOCK):
            x = self.pool[self.rows[lo:lo + BLOCK]]
            eta = np.clip(risk_median.dot_bf16x3(x, self.beta), -30.0, 30.0)
            r = np.exp(eta.astype(np.float32))
            risk.append(r)
            median.append(risk_median.first_median(
                np.exp(-h0[None, :] * r[:, None]), grid))
        return self.compare(np.arange(len(self.rows)), np.concatenate(risk),
                            np.concatenate(median))
