"""Closed-loop solves: one analyst fitting a penalized Cox model back to
back, each solve from beta = 0 to the solver's own stopping rule.

The entry is ``core.solvers.fit_cd_tol`` on cohorts made in set-up from
the configuration's generator. The traffic file names the pool of
cohorts (``data_seeds``); every run solves the same pool, in an order
drawn from ``--seed``, and the window closes at the end of the first
whole pass over the pool that ends after ``--seconds``, so every run
does the same work. ``solve_s`` is the window over the solves in it.

The check: for every cohort, the coefficients and objective the timed
solves returned, against the plain Breslow reference in float64: the
relative gap between the returned objective and the reference objective
at the returned coefficients, and the largest violation of the
optimality conditions. Repeated solves of one cohort must return the
same answer bit for bit.

With ``--trace 1`` one more solve follows the window, traced for its
first ``trace_seconds``.
"""
from __future__ import annotations

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

    def _cohort(self, data_seed):
        c = self.cfg
        return datagen.appc(data_seed, c["n"], c["p"], c["k"], c["rho"],
                            c["s"], c["censor_scale"])

    def setup(self):
        import jax
        import jax.numpy as jnp

        from repro.core import cox, solvers

        self.solvers = solvers
        seeds = list(self.traffic["data_seeds"])
        self.host = [self._cohort(s) for s in seeds]
        dev = self.devices[0]
        self.data = [jax.device_put(cox.prepare(jnp.asarray(x),
                                                jnp.asarray(t),
                                                jnp.asarray(d)), dev)
                     for x, t, d, _ in self.host]
        rng = np.random.default_rng(abs(int(self.seed)))
        self.order = [int(i) for i in rng.permutation(len(self.data))]
        sv = self.cfg["solver"]
        self.kw = dict(lam1=float(self.cfg["lambda1"]),
                       lam2=float(self.cfg["lambda2"]),
                       max_iters=int(sv["max_iters"]), tol=float(sv["tol"]),
                       method=sv["method"])
        # compile (or read the cache) the solve's one program and run it
        # once; tol is traced, so a huge one stops it after one sweep
        out = solvers.fit_cd_tol(self.data[0], **dict(self.kw, tol=1e30))
        jax.block_until_ready(out)

    def _solve(self, i):
        import jax

        with tracing.annotate("bench.solve"):
            out = self.solvers.fit_cd_tol(self.data[i], **self.kw)
            jax.block_until_ready(out)
        return out

    def window(self, seconds, capture):
        results = []
        t0 = time.perf_counter()
        k = 0
        while True:
            i = self.order[k % len(self.order)]
            results.append((i, self._solve(i)))
            k += 1
            if k % len(self.order) == 0 and \
                    time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        # numpy, not jax indexing: an index into a device array compiles
        self.results = [(i, np.asarray(r.beta),
                         float(np.asarray(r.objective)[0]), int(r.n_iters))
                        for i, r in results]
        sweeps = [r[3] for r in self.results]
        self.counters.update(solves=len(results), window_s=elapsed,
                             sweeps_mean=float(np.mean(sweeps)),
                             sweep_s=elapsed / sum(sweeps))
        by_cohort = {self.traffic["data_seeds"][i]: n
                     for i, _, _, n in self.results}
        print(f"fit: {len(results)} solves in {elapsed:.3f} s, sweeps by "
              f"cohort {by_cohort}", file=self.log)
        if capture is not None:
            self._traced(capture)
        return {"solve_s": elapsed / len(results)}

    def _traced(self, capture):
        """Trace the first ``trace_seconds`` of one more solve: a whole
        solve is some thousands of device operations per sweep, too many
        to trace, and every stretch of it is alike."""
        import jax

        capture.start()
        with tracing.window(capture):
            with tracing.annotate("bench.solve"):
                t0 = time.perf_counter()
                out = self.solvers.fit_cd_tol(self.data[self.order[0]],
                                              **self.kw)
                time.sleep(self.traffic["trace_seconds"])
        capture.stop()
        jax.block_until_ready(out)
        if time.perf_counter() - t0 < self.traffic["trace_seconds"]:
            print("fit: the traced solve ended inside the traced stretch",
                  file=self.log)

    def release(self):
        self.data = None

    def check(self):
        lim = self.cfg["limits"]["fit"]
        first, failed = {}, 0
        for i, beta, obj, _ in self.results:
            if i not in first:
                first[i] = (beta, obj)
            elif not (np.array_equal(first[i][0], beta)
                      and first[i][1] == obj):
                failed += 1
        failed += sum(not np.isfinite(b).all() for b, _ in first.values())
        nums = self.compare(first)
        return {"correct": failed == 0 and len(first) == len(self.host),
                "attempted": len(self.results), "failed": failed,
                "checks": {k: {"value": v, "limit": lim[k]}
                           for k, v in nums.items()}}

    def compare(self, answers):
        """The numbers compared, from {cohort: (beta, objective)}."""
        obj_gap = kkt = 0.0
        lam1, lam2 = self.kw["lam1"], self.kw["lam2"]
        for i, (beta, obj) in answers.items():
            x, t, d, _ = self.host[i]
            ref = breslow.objective(x, t, d, beta, lam1, lam2)
            obj_gap = max(obj_gap, float(abs(obj - ref) / abs(ref)))
            kkt = max(kkt, breslow.kkt_violation(x, t, d, beta, lam1, lam2))
        return {"objective_gap": obj_gap, "kkt": kkt}

    def control(self):
        """The same solves through the program's bfloat16 path (the data
        in bfloat16, so every statistic is computed in it), compared as
        the timed solves are."""
        import jax.numpy as jnp

        from repro.core import cox

        answers = {}
        for i, (x, t, d, _) in enumerate(self.host):
            data = cox.prepare(jnp.asarray(x, jnp.bfloat16), jnp.asarray(t),
                               jnp.asarray(d))
            r = self.solvers.fit_cd_tol(data, **self.kw)
            answers[i] = (np.asarray(r.beta, np.float32),
                          float(np.asarray(r.objective)[0]))
        return self.compare(answers)
