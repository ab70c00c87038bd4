"""Closed-loop beam searches: one analyst selecting a k-sparse Cox model
back to back, each search from the empty support.

The entry is ``core.beam.beam_search`` at the traffic file's ``search``
settings (its own defaults but for k, beam width and expansion), on
cohorts made in set-up from the configuration's generator. As
``drivers/fit.py`` does, every run
searches the same pool of cohorts (``data_seeds``) in an order drawn
from ``--seed``, and the window closes at the end of the first whole
pass over the pool that ends after ``--seconds``. ``solve_s`` is the
window over the searches in it.

Set-up compiles the search's programs with one narrow search (beam width
and expansion one, the same shapes). With ``--trace 1`` the program's
spans go to a file for the window (``beam.score`` and ``beam.finetune``
over the window are the per-layer shares), and one more search follows
the window, traced for its first ``trace_seconds``.

The check, per cohort: the unpenalized loss the search returned for its
best support of size k, against the float64 Breslow reference at the
returned coefficients; how far those coefficients are from optimal on
their support (the largest gradient of loss + lam2 |beta|^2 over the
support, over the largest gradient of the loss at beta = 0); and the
number of searches whose final support is not of size k. Repeated
searches of one cohort must return the same answer bit for bit.
"""
from __future__ import annotations

import json
import os
import tempfile
import time

import numpy as np

import datagen
import tracing
from reference import breslow


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
        import jax
        import jax.numpy as jnp

        from repro.core import beam, cox

        self.beam = beam
        c = self.cfg
        self.host = [datagen.appc(s, c["n"], c["p"], c["k"], c["rho"],
                                  c["s"], c["censor_scale"])
                     for s in self.traffic["data_seeds"]]
        dev = self.devices[0]
        self.data = [jax.device_put(cox.prepare(jnp.asarray(x),
                                                jnp.asarray(t),
                                                jnp.asarray(d)), dev)
                     for x, t, d, _ in self.host]
        rng = np.random.default_rng(abs(int(self.seed)))
        self.order = [int(i) for i in rng.permutation(len(self.data))]
        self.kw = dict(self.traffic["search"])
        beam.beam_search(self.data[0], **dict(self.kw, beam_width=1,
                                              n_expand=1))

    def _search(self, i):
        with tracing.annotate("bench.search"):
            return self.beam.beam_search(self.data[i], **self.kw)

    def window(self, seconds, capture):
        from repro.obs import trace as obs_trace

        span_file = None
        if capture is not None:
            fd, span_file = tempfile.mkstemp(prefix="bench_spans_",
                                             suffix=".jsonl")
            os.close(fd)
            obs_trace.configure(span_file)
        results, ends = [], []
        try:
            t0 = time.perf_counter()
            k = 0
            while True:
                i = self.order[k % len(self.order)]
                results.append((i, self._search(i)))
                ends.append(time.perf_counter() - t0)
                k += 1
                if k % len(self.order) == 0 and ends[-1] >= seconds:
                    break
            elapsed = ends[-1]
        finally:
            if span_file is not None:
                obs_trace.configure(None)
        if span_file is not None:
            with open(span_file) as f:
                self.spans = [json.loads(ln) for ln in f if ln.strip()]
            os.remove(span_file)
        self.results = [(i, r.supports[-1], r.betas[-1], r.losses[-1])
                        for i, r in results]
        self.counters.update(searches=len(results), window_s=elapsed)
        losses = {self.traffic["data_seeds"][i]: loss
                  for i, _, _, loss in self.results}
        each = np.diff([0.0] + ends)
        print(f"beam: {len(results)} searches in {elapsed:.3f} s "
              f"({', '.join(f'{s:.3f}' for s in each)}), final loss by "
              f"cohort {losses}", file=self.log)
        if capture is not None:
            self._traced(capture)
        return {"solve_s": elapsed / len(results)}

    def span_share(self, name):
        """Share of the window (%) inside the program's spans of this
        name; None where none was recorded."""
        durs = [s["dur_s"] for s in self.spans if s.get("name") == name]
        if not durs:
            return None
        return 100.0 * sum(durs) / self.counters["window_s"]

    def _traced(self, capture):
        """Trace the first ``trace_seconds`` of one more search: a whole
        search is some millions of device operations, and every stretch
        of it is alike. The search runs on a thread of its own, since
        it returns to the host after every call."""
        import threading

        errors = []

        def run():
            try:
                self._search(self.order[0])
            except Exception as e:      # re-raised on the caller's thread
                errors.append(e)

        worker = threading.Thread(target=run, name="bench-search")
        capture.start()
        with tracing.window(capture):
            worker.start()
            time.sleep(self.traffic["trace_seconds"])
        capture.stop()
        worker.join()
        if errors:
            raise errors[0]

    def release(self):
        self.data = None

    def check(self):
        lim = self.traffic["limits"]
        k = self.kw["k"]
        first, failed = {}, 0
        for i, supp, beta, loss in self.results:
            if i not in first:
                first[i] = (beta, loss)
            elif not (np.array_equal(first[i][0], beta)
                      and first[i][1] == loss):
                failed += 1
        failed += sum(not np.isfinite(b).all() for b, _ in first.values())
        nums = self.compare(first)
        nums["support_off"] = float(sum(
            len(set(int(j) for j in supp)) != k
            or int(np.count_nonzero(beta)) != k
            for _, supp, beta, _ in self.results))
        return {"correct": failed == 0 and len(first) == len(self.host),
                "attempted": len(self.results), "failed": failed,
                "checks": {name: {"value": v, "limit": lim[name]}
                           for name, v in nums.items()}}

    def compare(self, answers):
        """The numbers compared, from {cohort: (beta, loss)}."""
        loss_gap = kkt = 0.0
        lam2 = self.kw["lam2"]
        for i, (beta, loss) in answers.items():
            x, t, d, _ = self.host[i]
            ref, g = breslow.loss_and_grad(x, t, d, beta)
            _, g0 = breslow.loss_and_grad(x, t, d, np.zeros_like(beta))
            loss_gap = max(loss_gap, float(abs(loss - ref) / abs(ref)))
            on = np.flatnonzero(beta)
            if on.size:
                g = g[on] + 2.0 * lam2 * np.asarray(beta, np.float64)[on]
                kkt = max(kkt, float(np.abs(g).max() / np.abs(g0).max()))
            else:
                kkt = max(kkt, 1.0)
        return {"loss_gap": loss_gap, "kkt_support": kkt}

    def control(self):
        """The same searches through the program's bfloat16 path (the data
        in bfloat16, so every statistic is computed in it), compared as
        the timed searches are."""
        import jax.numpy as jnp

        from repro.core import cox

        answers = {}
        for i, (x, t, d, _) in enumerate(self.host):
            data = cox.prepare(jnp.asarray(x, jnp.bfloat16), jnp.asarray(t),
                               jnp.asarray(d))
            r = self.beam.beam_search(data, **self.kw)
            answers[i] = (np.asarray(r.betas[-1], np.float32),
                          float(r.losses[-1]))
        return self.compare(answers)
