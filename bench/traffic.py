"""The one general traffic generator.

A traffic mix is a data file, ``bench/traffic/<name>.json``, whose
``kind`` picks one of the drivers below and whose other keys are its
parameters. Every driver has the same life: ``setup`` builds what the
window drives, ``warm`` runs each shape the window will use on inputs
outside the window's own, ``window`` runs for the given seconds and
returns a ``Run`` record (samples and counters), and ``judge`` compares
a seeded sample of the window's answers with the plain reference.

The inputs of a window are drawn from ``--seed`` on the device
(``device_draw``): the order in which a fixed universe of search seeds is
cycled, or the arrival gaps and request keys of an open loop. Every seed
so gets the same set of work, in another order.
"""
from __future__ import annotations

import json
import os
import random
import threading
import time
import urllib.request
from typing import Callable, Dict, List

import numpy as np

from . import reference

HERE = os.path.dirname(os.path.abspath(__file__))

#: host spans the benchmark writes on the profiler's clock
ANNOTATIONS = ("search", "sweep", "request", "window")


def annotate(name: str):
    """A host span on the profiler's clock (names the device's gaps)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def _key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.key(seed % (1 << 32)),
                              (seed >> 32) % (1 << 31))


def device_draw(kind: str, n: int, seed: int, **kw) -> np.ndarray:
    """Seeded draw on the device, read back to the host.

    ``permutation``: a permutation of ``range(n)``. ``zipf``: ``n`` key
    ranks under Zipf(``s``) over ``kw["keys"]`` keys. ``gamma``: ``n``
    gaps of mean 1 and coefficient of variation ``kw["cv"]``."""
    import jax
    import jax.numpy as jnp
    fn = _DRAWS.get((kind, n, tuple(sorted(kw.items()))))
    if fn is None:
        if kind == "permutation":
            def f(k):
                return jax.random.permutation(k, n)
        elif kind == "zipf":
            logits = -kw["s"] * jnp.log(jnp.arange(1, kw["keys"] + 1,
                                                   dtype=jnp.float32))

            def f(k):
                return jax.random.categorical(k, logits, shape=(n,))
        elif kind == "gamma":
            shape = 1.0 / kw["cv"] ** 2

            def f(k):
                return jax.random.gamma(k, shape, (n,)) / shape
        else:
            raise ValueError(f"unknown draw {kind!r}")
        fn = _DRAWS[(kind, n, tuple(sorted(kw.items())))] = jax.jit(f)
    return np.asarray(fn(_key(seed)))


_DRAWS: Dict = {}


class Run(dict):
    """What a window leaves for the metric readers: ``window_s``,
    ``completed``, per-item samples (``searches``, ``sweeps`` or
    ``requests``), and in a traced run ``counters`` and ``spans`` (the
    program's obs counters and span durations) and ``trace``."""


# ---------------------------------------------------------------------------
# search_loop: whole-network mapping searches, closed loop, one caller.
# ---------------------------------------------------------------------------

class SearchLoop:
    """Back-to-back searches, each with a fresh engine. Parameters:
    ``n_candidates``, ``max_steps``, ``universe`` (the search seeds
    ``1..universe``, cycled in an order permuted by the run's seed),
    ``judged`` (searches the reference judges layer by layer),
    ``limits``. Cycling a small universe gives every run nearly the same
    work: only the last, partial cycle differs between seeds."""

    def __init__(self, deployment, params: Dict, seed: int):
        self.dep, self.p, self.seed = deployment, params, seed

    def _cfg(self, s: int):
        return self.dep.search_config(self.p["n_candidates"],
                                      self.p["max_steps"], s)

    def setup(self) -> None:
        self.net = self.dep.plain_network()

    def warm(self) -> None:
        # one search outside the universe: imports, lazy tables, allocator
        self.dep.search(self._cfg(self.p["universe"] + 1))
        device_draw("permutation", self.p["universe"], self.seed + 1)

    def window(self, seconds: float) -> Run:
        order = device_draw("permutation", self.p["universe"], self.seed)
        t0 = time.perf_counter()
        out = []
        while time.perf_counter() - t0 < seconds:
            s = int(order[len(out) % len(order)]) + 1
            with annotate("search"):
                a = time.perf_counter()
                ans = self.dep.search(self._cfg(s))
                ans["wall_s"] = time.perf_counter() - a
            out.append(ans)
        return Run(window_s=time.perf_counter() - t0, searches=out,
                   completed=len(out), attempted=len(out), failed=0)

    def judge(self, run: Run, dtype=np.float64) -> List:
        """Each judged search: its committed mappings against the
        candidate pools and forward scores of the reference, and its
        reported latencies and energy against the reference's."""
        rng = random.Random(self.seed)
        picks = rng.sample(range(len(run["searches"])),
                           min(self.p["judged"], len(run["searches"])))
        net = reference.Network(self.net["layers"], self.net["edges"])
        off, gap = 0, 0.0
        for k in picks:
            ans = run["searches"][k]
            ref = reference.search(
                net, self.dep.cfg["arch"], self._params(ans["seed"]),
                chosen=ans["chosen"], check_layers=set(range(
                    len(net.layers))), dtype=dtype)
            off += ref["off_pool"]
            gap = max(gap, ref["choice_excess"],
                      abs(ans["total"] - ref["total"]) / ref["total"],
                      abs(ans["energy"] - ref["energy"]) / ref["energy"],
                      max(abs(a - b) for a, b in zip(ans["ends"],
                                                     ref["ends"]))
                      / ref["total"])
        lim = self.p["limits"]
        return [("off_pool_layers", off, lim["off_pool_layers"]),
                ("answer_gap", gap, lim["answer_gap"])]

    def _params(self, s: int) -> Dict:
        return {"seed": s, "n_candidates": self.p["n_candidates"],
                "max_steps": self.p["max_steps"],
                "objective": self.dep.cfg["objective"]}


# ---------------------------------------------------------------------------
# dse_loop: architecture sweeps back to back, one caller.
# ---------------------------------------------------------------------------

class DSELoop:
    """``run_dse`` sweeps back to back, each with a fresh engine and an
    in-memory journal. Parameters: ``explorer``, ``budget``,
    ``n_candidates``, ``max_steps``, ``universe`` (sweep seeds, cycled in
    a permuted order), ``judged`` (evaluated points the reference
    re-searches), ``limits``."""

    def __init__(self, deployment, params: Dict, seed: int):
        self.dep, self.p, self.seed = deployment, params, seed

    def setup(self) -> None:
        self.net = self.dep.plain_network()

    def warm(self) -> None:
        from . import program
        program.dse_sweep(self.dep, dict(self.p, budget=2),
                          self.p["universe"] + 1)
        device_draw("permutation", self.p["universe"], self.seed + 1)

    def window(self, seconds: float) -> Run:
        from . import program
        order = device_draw("permutation", self.p["universe"], self.seed)
        t0 = time.perf_counter()
        sweeps = []
        while time.perf_counter() - t0 < seconds:
            s = int(order[len(sweeps) % len(order)]) + 1
            with annotate("sweep"):
                sweeps.append(program.dse_sweep(self.dep, self.p, s))
        evaluated = sum(s["stats"]["evaluated"] for s in sweeps)
        return Run(window_s=time.perf_counter() - t0, sweeps=sweeps,
                   completed=evaluated, attempted=evaluated, failed=0)

    def judge(self, run: Run, dtype=np.float64) -> List:
        """A seeded sample of evaluated points, each searched again by
        the reference on the point's architecture: the latency and energy
        the sweep recorded against the reference's."""
        pts = [(s["seed"], r) for s in run["sweeps"] for r in s["records"]]
        rng = random.Random(self.seed)
        gap = 0.0
        for seed, rec in rng.sample(pts, min(self.p["judged"], len(pts))):
            gap = max(gap, _point_gap(self.net, self.dep.cfg, self.p, seed,
                                      rec, dtype))
        return [("answer_gap", gap, self.p["limits"]["answer_gap"])]


def _point_gap(net: Dict, cfg: Dict, p: Dict, seed: int, rec: Dict,
               dtype) -> float:
    ref = reference.search(
        reference.Network(net["layers"], net["edges"]),
        reference.arch_for_point(cfg["arch"], rec["point"]),
        {"seed": seed, "n_candidates": p["n_candidates"],
         "max_steps": p["max_steps"], "objective": cfg["objective"]},
        dtype=dtype)
    return max(abs(rec["total_ns"] - ref["total"]) / ref["total"],
               abs(rec["energy_pj"] - ref["energy"]) / ref["energy"])


# ---------------------------------------------------------------------------
# serve_open_loop: HTTP clients on a fixed-rate open loop.
# ---------------------------------------------------------------------------

def http_post(url: str, body: Dict, timeout: float = 300.0):
    """POST one request; (status, body), non-2xx bodies included."""
    import urllib.error
    r = urllib.request.Request(
        url + "/v1/mapping", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


class ServeLoop:
    """An in-process mapping server and open-loop HTTP clients.

    Requests are ``request`` with the objective and seed of one key of
    ``objectives`` x ``range(1, seeds + 1)``; keys are drawn Zipf(``zipf_s``)
    over ranks, ranks mapped to keys by a permutation of the seed; gaps
    between arrivals are gamma with mean ``1 / rate`` and coefficient of
    variation ``cv``. Each request is timed from when it was due. Warm-up
    uses seeds above the window's. ``judged`` responses are searched again
    by the reference at their best point; repeats of one key must carry
    the first response's frontier and best point byte for byte."""

    def __init__(self, deployment, params: Dict, seed: int):
        self.dep, self.p, self.seed = deployment, params, seed
        self.server = None

    def _keys(self):
        return [(o, s) for o in self.p["objectives"]
                for s in range(1, self.p["seeds"] + 1)]

    def _body(self, objective: str, s: int) -> Dict:
        from . import program
        return dict(self.p["request"], objective=objective, seed=s,
                    network=program.network_name(self.dep.cfg))

    def setup(self) -> None:
        from . import program
        self.net = self.dep.plain_network()
        self.server = program.Server(self.p)

    def warm(self) -> None:
        n_max = int(self.p["rate"] * self.p["max_seconds"]) + 1
        for k in ("permutation", "zipf", "gamma"):
            self._draw(k, n_max, self.seed + 1)
        base = self.p["seeds"] + 1
        for o in self.p["objectives"]:
            for body in (self._body(o, base), self._body(o, base)):
                code, _ = http_post(self.server.url, body)
                if code != 200:
                    raise RuntimeError(f"warm-up request failed: {code}")
        self.n_warm = len(self.server.flight())

    def _draw(self, kind: str, n: int, seed: int):
        if kind == "permutation":
            return device_draw(kind, len(self._keys()), seed)
        if kind == "zipf":
            return device_draw(kind, n, seed, s=self.p["zipf_s"],
                               keys=len(self._keys()))
        return device_draw(kind, n, seed, cv=self.p["cv"])

    def window(self, seconds: float) -> Run:
        from concurrent.futures import ThreadPoolExecutor
        n_max = int(self.p["rate"] * self.p["max_seconds"]) + 1
        keys = self._keys()
        perm = self._draw("permutation", n_max, self.seed)
        ranks = self._draw("zipf", n_max, self.seed)
        gaps = self._draw("gamma", n_max, self.seed).astype(np.float64) \
            / self.p["rate"]
        due = np.cumsum(gaps) - gaps[0]
        n = int(np.searchsorted(due, seconds))
        if n >= n_max:
            raise RuntimeError("raise max_seconds: the draw ran out")
        sent = [None] * n
        lat = [None] * n
        answers = [None] * n
        lock = threading.Lock()
        t0 = time.perf_counter()

        def one(i):
            with annotate("request"):
                sent[i] = time.perf_counter() - t0
                o, s = keys[int(perm[int(ranks[i])])]
                code, body = http_post(self.server.url, self._body(o, s))
                done = time.perf_counter() - t0
            with lock:
                lat[i] = done - due[i]
                answers[i] = (o, s, code, body)

        with ThreadPoolExecutor(max_workers=self.p["clients"]) as pool:
            futs = []
            for i in range(n):
                wait = float(due[i]) - (time.perf_counter() - t0)
                if wait > 0:
                    time.sleep(wait)
                futs.append(pool.submit(one, i))
            for f in futs:
                f.result()
        late = np.asarray(sent) - due[:n]
        failed = sum(1 for a in answers if a[2] != 200)
        self.run = Run(window_s=float(seconds), requests=[
            {"latency_s": lat[i], "objective": answers[i][0],
             "seed": answers[i][1], "code": answers[i][2],
             "body": answers[i][3]} for i in range(n)],
            completed=n - failed, attempted=n, failed=failed,
            late_p50_s=float(np.median(late)), late_max_s=float(late.max()))
        return self.run

    def release(self) -> None:
        """Stop the server; its drained flight records join the run."""
        if self.server is not None:
            self.server.close()
            if getattr(self, "run", None) is not None:
                self.run["flight"] = self.server.flight()[self.n_warm:]
            self.server = None

    def judge(self, run: Run, dtype=np.float64) -> List:
        ok = [r for r in run["requests"] if r["code"] == 200]
        first: Dict = {}
        mismatch = 0
        for r in ok:
            k = (r["objective"], r["seed"])
            sig = (r["body"]["frontier_json"],
                   json.dumps(r["body"]["best"], sort_keys=True))
            if first.setdefault(k, sig) != sig:
                mismatch += 1
        rng = random.Random(self.seed)
        gap = 0.0
        cfg = dict(self.dep.cfg)
        for r in rng.sample(ok, min(self.p["judged"], len(ok))):
            cfg["objective"] = r["objective"]
            gap = max(gap, _point_gap(self.net, cfg, self.p["request"],
                                      r["seed"], r["body"]["best"], dtype))
        lim = self.p["limits"]
        return [("replay_mismatch", mismatch, lim["replay_mismatch"]),
                ("failed_requests", run["failed"], lim["failed_requests"]),
                ("answer_gap", gap, lim["answer_gap"])]


DRIVERS: Dict[str, Callable] = {"search_loop": SearchLoop,
                                "dse_loop": DSELoop,
                                "serve_open_loop": ServeLoop}


def load(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "traffic", name + ".json")) as fh:
        return json.load(fh)


def make(traffic: Dict, deployment, seed: int):
    return DRIVERS[traffic["kind"]](deployment, traffic, seed)
