"""The traffic generator: drives a solver as a traffic file says.

Every traffic mix is a data file, ``bench/traffic/<mix>.json``, of
parameters only, and this one generator reads them all:

* ``step``: the calls of one step, in order, each ``{"op": ...}``:

  - ``factor``: ``OOCSolver.factor(a, materialize=False)`` on the step's
    matrix (see ``theta``);
  - ``logdet``: the log-determinant, read from the tile store;
  - ``solve``: ``solve(b)`` for ``"rhs"`` right-hand sides drawn from the
    seed and the step's index.

  A step with no ``factor`` runs against the factor made in set-up.
* ``theta``: the covariance parameter of each step's matrix, as an
  optimiser moves it.  Step ``k`` of a run sets the nugget to the
  configuration's nugget times ``nugget_scale[(k + o) % len]``, with the
  offset ``o`` drawn from the seed; the matrix's diagonal is rewritten in
  place.  Set-up warms up at ``warmup_nugget_scale``, which no step uses,
  and no two steps in a row share a scale, so a factor that leaves the
  store as the step before left it is judged against a matrix it did not
  factor.
* ``loop``: ``closed``, one client that sends its next step when the last
  has returned; or ``open``, steps that arrive at Poisson times of
  ``rate_per_s`` drawn from the seed, served in order of arrival.
  ``clients`` is 1: a solver holds one factor.
* ``metrics``: the cell's end-to-end metrics that the window gives, each
  the name of one of ``STATS``.
* ``check_rhs``: right-hand sides solved against the last factor once the
  window has closed, to judge it.

Each call runs inside a ``bench.<op>`` profiler span, and the window
inside ``bench.window``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks

OPS = ("factor", "logdet", "solve")
LOOPS = ("closed", "open")
STATS = ("step_s", "steps_per_s", "latency_p50_ms", "latency_p95_ms")
SPAN = "bench."


def validate(traffic: dict) -> None:
    step = traffic.get("step")
    if not step or any(c.get("op") not in OPS for c in step):
        raise ValueError(f"traffic step {step!r}: a list of calls, each "
                         f"with an op of {OPS}")
    if any(c["op"] == "solve" and int(c.get("rhs", 0)) < 1 for c in step):
        raise ValueError("a solve call needs rhs >= 1")
    if traffic.get("loop") not in LOOPS:
        raise ValueError(f"traffic loop {traffic.get('loop')!r}: one of "
                         f"{LOOPS}")
    if traffic["loop"] == "open" and not traffic.get("rate_per_s", 0) > 0:
        raise ValueError("an open loop needs rate_per_s > 0")
    if traffic.get("clients") != 1:
        raise ValueError("one client: a solver holds one factor")
    th = traffic.get("theta", {})
    scales = th.get("nugget_scale", [])
    if not scales or min(scales) <= 0:
        raise ValueError("theta.nugget_scale: a list of positive scales")
    if "factor" in [c["op"] for c in step] and (
            len(scales) < 2
            or any(scales[i] == scales[i - 1] for i in range(len(scales)))):
        raise ValueError("theta.nugget_scale: two steps in a row share a "
                         "scale")
    if th.get("warmup_nugget_scale") in scales \
            or not th.get("warmup_nugget_scale", 0) > 0:
        raise ValueError("theta.warmup_nugget_scale: positive, and used "
                         "by no step")
    unknown = set(traffic.get("metrics", {}).values()) - set(STATS)
    if not traffic.get("metrics") or unknown:
        raise ValueError(f"traffic metrics: each one of {STATS}")
    if int(traffic.get("check_rhs", 0)) < 1:
        raise ValueError("check_rhs must be >= 1")


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([seed % 2**64, *key])


def check_rhs(n: int, seed: int, k: int) -> np.ndarray:
    return _rng(seed, 1).standard_normal((n, k))


class Driver:
    """One client's steps on one solver and one host matrix."""

    def __init__(self, solver, a: np.ndarray, config: dict, traffic: dict,
                 seed: int):
        validate(traffic)
        self.solver, self.a, self.traffic, self.seed = (solver, a, traffic,
                                                        seed)
        self.n = a.shape[0]
        m = config["matrix"]
        self.unit = m["sigma2"] * m["nugget"]
        self.base = a.diagonal().copy()
        th = traffic["theta"]
        self.scales = list(th["nugget_scale"])
        self.warm = th["warmup_nugget_scale"]
        self.offset = int(_rng(seed, 0).integers(len(self.scales)))
        self.factored = None    # diagonal shift of the factor in the store
        self.solves = []        # (shift, b, x) of every solve in a step

    def shift(self, k: int) -> float:
        """What step ``k`` (-1: set-up's) adds to the configuration's
        diagonal."""
        scale = self.warm if k < 0 else self.scales[
            (k + self.offset) % len(self.scales)]
        return self.unit * (scale - 1.0)

    def _factor(self, k: int) -> None:
        import jax
        s = self.shift(k)
        with jax.profiler.TraceAnnotation(SPAN + "factor"):
            self.a.flat[::self.n + 1] = self.base + s
            self.solver.factor(self.a, materialize=False)
        self.factored = s

    def step(self, k: int) -> bool:
        """Runs step ``k``; False where a call failed: a log-determinant
        that is not finite, or a solve that the substitution refuses or
        that is not finite."""
        import jax
        ok = True
        for call in self.traffic["step"]:
            op = call["op"]
            if op == "factor":
                self._factor(k)
                continue
            with jax.profiler.TraceAnnotation(SPAN + op):
                try:
                    if op == "logdet":
                        ok &= bool(np.isfinite(self.solver.logdet()))
                        continue
                    b = _rng(self.seed, 2, k + 1).standard_normal(
                        (self.n, int(call["rhs"])))
                    x = self.solver.solve(b)
                except ValueError:      # the factor lost definiteness
                    ok = False
                    continue
            ok &= bool(np.isfinite(x).all())
            self.solves.append((self.factored, b, x))
        return ok

    def warm_up(self) -> bool:
        """Set-up's step, at a nugget that no window step uses, after a
        factor where the step has none; its solves are not judged."""
        if "factor" not in [c["op"] for c in self.traffic["step"]]:
            self._factor(-1)
        ok = self.step(-1)
        self.solves.clear()
        return ok

    def finish(self) -> list:
        """Everything to judge, as ``[(shift, b, x)]``: the window's
        solves and ``check_rhs`` solved against the last factor.  Drops
        the solver and restores the matrix's own diagonal."""
        out = list(self.solves)
        b = check_rhs(self.n, self.seed, int(self.traffic["check_rhs"]))
        try:
            x = self.solver.solve(b)
        except ValueError:  # non-finite factor entries, refused
            x = np.full_like(b, np.nan)
        out.append((self.factored, b, x))
        self.solver = None
        self.a.flat[::self.n + 1] = self.base
        return out


def window(d: Driver, seconds: float) -> dict:
    """Steps until ``seconds`` have passed: back to back in a closed loop,
    in an open loop every step that arrives before then.  Every step
    started is finished and counted.  Returns the steps, how many failed,
    the seconds from the window's start to the last step's end, and the
    statistics of ``STATS``."""
    import jax
    open_loop = d.traffic["loop"] == "open"
    gaps = _rng(d.seed, 3)
    count = failed = 0
    arrive, latency = 0.0, []
    with jax.profiler.TraceAnnotation(SPAN + "window"):
        t0 = time.perf_counter()
        while True:
            if open_loop:
                wait = arrive - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
            else:
                arrive = time.perf_counter() - t0
            failed += not d.step(count)
            count += 1
            done = time.perf_counter() - t0
            latency.append(done - arrive)
            if open_loop:
                arrive += gaps.exponential(1.0 / d.traffic["rate_per_s"])
                if arrive >= seconds:
                    break
            elif done >= seconds:
                break
    ms = np.asarray(latency) * 1e3
    return {"steps": count, "failed": failed, "elapsed": done,
            "stats": {"step_s": done / count, "steps_per_s": count / done,
                      "latency_p50_ms": float(np.percentile(ms, 50)),
                      "latency_p95_ms": float(np.percentile(ms, 95))}}


def judge(a: np.ndarray, answers: list) -> float:
    """The largest normwise backward error of the answers, each against
    the matrix of the factor it was solved with: ``a`` plus its shift on
    the diagonal.  Answers of one factor are judged together."""
    by_shift: dict = {}
    for s, b, x in answers:
        if s is None:
            return float("nan")
        by_shift.setdefault(s, []).append((b, x))
    return max(checks.backward_error(a, np.hstack([x for _, x in v]),
                                     np.hstack([b for b, _ in v]), shift=s)
               for s, v in by_shift.items())
