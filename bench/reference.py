"""Plain reference of the overlap-driven mapping search.

Independent of the program under test: it imports nothing from ``repro``
and reads only plain data (layer shapes, dependency edges, architecture
numbers, search parameters, and the answer to be judged). It follows the
definitions of Fast-OverlaPIM (arXiv:2407.00604, Sections IV-C to IV-K):

* candidates: the heuristic output-stationary mapping plus seeded random
  tilings and loop orders, exactly the sampled search space the searcher
  draws from (the space is part of the search's definition);
* a producer output element is finished at the producer time step of its
  coordinates with every reduction loop at its last iteration; the step
  is computed densely over the whole [K, P, Q] output;
* a consumer (bank, step) tile is ready when the latest producer step
  inside its projected input box has finished on every bank; the box
  maximum is a plain range-maximum over the dense array;
* the transformation re-sorts tiles by ready time and deals them out
  round-robin over the banks, charging one tile move to each re-homed
  tile;
* the search commits layers in order, each to the candidate with the
  least forward score.

All times are carried in ``dtype`` (float64 by default). Running it in
float32 is the lower-precision control.
"""
from __future__ import annotations

import functools
import math
import random
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

DIMS = ("K", "C", "P", "Q", "R", "S", "N")
OUT = ("K", "P", "Q")
RED = ("C", "R", "S")
_STREAM_GROUP = {"N": 0, "P": 0, "Q": 0, "K": 1, "C": 2, "R": 2, "S": 2}

Blocks = Tuple[Tuple[Tuple[str, int, bool], ...], ...]


# ---------------------------------------------------------------------------
# Architecture (plain numbers) and one mapping's derived structure.
# ---------------------------------------------------------------------------

class Arch:
    """Levels top to bottom: name, fanout, read/write bandwidth (B/ns)."""

    def __init__(self, d: Dict):
        self.levels = [dict(lv) for lv in d["levels"]]
        self.target = [lv["name"] for lv in self.levels].index(
            d["target_level"])
        self.word_bits = d["word_bits"]
        self.word_bytes = self.word_bits / 8.0
        self.timing = dict(d["timing"])
        ops = self.levels[-1].get("pim_ops") or {}
        n, aap = self.word_bits, self.timing["t_rc"]
        add = (4 * n + 1) * aap
        self.t_add = ops.get("add", add)
        self.t_mul = ops.get("mul", n * add)
        bws = [lv["read_bw"] for lv in self.levels if lv.get("read_bw")]
        self.move_ns_per_byte = 1.0 / (min(bws) if bws else 16.0)
        chan = self.levels[min(1, len(self.levels) - 1)]
        self.write_bw = chan.get("write_bw") or 16.0

    def fanout(self, li: int) -> int:
        return self.levels[li]["fanout"]


def arch_for_point(base: Dict, point: Dict) -> Dict:
    """The DRAM-PIM architecture of one design point: channels, banks and
    columns set the Channel, Bank and Column fanouts; ``word_bits`` sets
    the word and scales the pinned add and multiply latencies by r and r^2
    (r = bits / 16, bit-serial arithmetic); ``timing_scale`` scales every
    timing parameter and op latency (a speed bin); ``target_level`` moves
    the overlap analysis."""
    bits = point["word_bits"]
    r, ts = bits / 16.0, point["timing_scale"]
    fan = {"Channel": point["channels_per_layer"],
           "Bank": point["banks_per_channel"],
           "Column": point["columns_per_bank"]}
    levels = []
    for lv in base["levels"]:
        lv = dict(lv, fanout=fan.get(lv["name"], lv["fanout"]))
        if lv.get("pim_ops"):
            ops = dict(lv["pim_ops"])
            if bits != 16:
                ops = {op: ns * {"add": r, "mul": r * r}.get(op, r)
                       for op, ns in ops.items()}
            if ts != 1.0:
                ops = {op: ns * ts for op, ns in ops.items()}
            lv["pim_ops"] = ops
        levels.append(lv)
    timing = dict(base["timing"])
    if ts != 1.0:
        for k in ("t_rc", "t_rcd", "t_ras", "t_cl", "t_rrd", "t_wr",
                  "t_ccd_s", "t_ccd_l"):
            timing[k] = timing[k] * ts
    return {"levels": levels, "target_level": point["target_level"],
            "word_bits": bits, "timing": timing}


class MapInfo:
    """Derived schedule structure of one mapping (blocks outer->inner)."""

    def __init__(self, layer: Dict, arch: Arch, blocks: Blocks):
        self.layer, self.arch, self.blocks = layer, arch, blocks
        t = arch.target
        nest = [(li, lp) for li, blk in enumerate(blocks) for lp in blk]
        rect = [(li, lp) for li, lp in nest
                if li < t or (li == t and not lp[2])]
        self.n_steps = math.prod(lp[1] for li, lp in rect if not lp[2])
        self.n_banks = math.prod(lp[1] for li, lp in rect if lp[2])
        self.n_cols = math.prod(lp[1] for li, lp in nest
                                if li == t and lp[2])
        cur = {d: layer[d] for d in DIMS}
        trest, brest = self.n_steps, self.n_banks
        # (dim, size, block extent per iteration, time stride, bank stride)
        self.loops = []
        for li, (d, size, spatial) in rect:
            cur[d] //= size
            if spatial:
                brest //= size
                self.loops.append((d, size, cur[d], 0, brest))
            else:
                trest //= size
                self.loops.append((d, size, cur[d], trest, 0))
        self.ext = cur
        self.nest = nest

    def tiles(self):
        """Lower corners (n_banks, n_steps) per dim of every tile."""
        b = np.arange(self.n_banks, dtype=np.int64)[:, None]
        s = np.arange(self.n_steps, dtype=np.int64)[None, :]
        lo = {d: np.zeros((self.n_banks, self.n_steps), np.int64)
              for d in DIMS}
        for d, size, blk, ts, bs in self.loops:
            idx = (b // bs) % size if bs else (s // ts) % size
            lo[d] = lo[d] + idx * blk
        hi = {d: lo[d] + self.ext[d] for d in DIMS}
        return lo, hi

    def finish_steps(self) -> np.ndarray:
        """Producer step at which each output element [K, P, Q] is done."""
        L = self.layer
        shape = (L["K"], L["P"], L["Q"])
        f = np.zeros(shape, dtype=np.int64)
        axes = {"K": 0, "P": 1, "Q": 2}
        for d, size, blk, ts, bs in self.loops:
            if bs:
                continue
            if d in axes:
                idx = (np.arange(L[d], dtype=np.int64) // blk) % size
                view = [1, 1, 1]
                view[axes[d]] = L[d]
                f = f + (idx * ts).reshape(view)
            else:                      # reduction / batch: last iteration
                f = f + (size - 1) * ts
        return f


def layer_perf(mi: MapInfo, dtype) -> Dict:
    """Per-mapping latency and energy (Section IV-C, Table I)."""
    a, L = mi.arch, mi.layer
    macs_step = math.prod(mi.ext[d] for d in DIMS)
    macs_per_col = math.ceil(macs_step / mi.n_cols)
    t_rw = a.timing["t_rcd"] + a.timing["t_cl"]
    n_red = math.prod(lp[1] for li, lp in mi.nest
                      if li == a.target and lp[2] and lp[0] in RED)
    out_cols = math.prod(lp[1] for li, lp in mi.nest
                         if li == a.target and lp[2] and lp[0] not in RED)
    tile_out = math.prod(mi.ext[d] for d in OUT)
    red = 0.0
    if n_red > 1:
        red = math.ceil(math.log2(n_red)) * math.ceil(tile_out / out_cols) \
            * (a.word_bytes * a.move_ns_per_byte + a.t_add)
    f = dtype.type
    step = f(macs_per_col * (a.t_mul + a.t_add + 2 * t_rw) + red)
    chans = math.prod(lp[1] for li, lp in mi.nest if li == 0 and lp[2])
    out_bytes = L["N"] * L["K"] * L["P"] * L["Q"] * a.word_bytes
    n = a.word_bits
    e_mac = (n + 1) * ((4 * n + 1) * a.timing["e_act"])
    macs = math.prod(L[d] for d in DIMS)
    return {
        "step": step,
        "compute": f(step * f(mi.n_steps)),
        "out_move": f(out_bytes / (a.write_bw * chans)),
        "tile_move": f(tile_out * a.word_bytes / a.write_bw),
        "tile_bytes": f(tile_out * a.word_bytes),
        "energy": f(macs * e_mac + out_bytes * 8 * a.timing["e_io"]),
        "move_pj_per_byte": f(8 * a.timing["e_io"]),
    }


# ---------------------------------------------------------------------------
# Candidate space (seeded sampler).
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _divisors(n: int) -> List[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def _valid(layer: Dict, arch: Arch, blocks: Blocks) -> bool:
    t, nl = arch.target, len(arch.levels)
    if len(blocks) != nl:
        return False
    prod = {d: 1 for d in DIMS}
    for li, blk in enumerate(blocks):
        sp, seen_sp = 1, False
        for d, size, spatial in blk:
            if size < 1:
                return False
            prod[d] *= size
            if spatial:
                seen_sp = True
                sp *= size
                if li >= nl - 1 or (d in RED and li != t):
                    return False
            elif seen_sp and li == t:
                return False
        if li < nl - 1 and sp > arch.fanout(li + 1):
            return False
    return all(prod[d] == layer[d] for d in DIMS)


def _blocks(arch: Arch, per_slot, rng, stream: bool) -> Blocks:
    out = []
    for li in range(len(arch.levels)):
        temporal = [(d, f, False) for d, f in per_slot[(li, False)].items()
                    if f > 1]
        spatial = []
        if li < len(arch.levels) - 1:
            spatial = [(d, f, True) for d, f in per_slot[(li, True)].items()
                       if f > 1]
        rng.shuffle(temporal)
        if stream:
            temporal.sort(key=lambda lp: _STREAM_GROUP[lp[0]])
        rng.shuffle(spatial)
        block = temporal + spatial
        if li != arch.target and not stream:
            rng.shuffle(block)
        out.append(tuple(block))
    return tuple(out)


def heuristic(layer: Dict, arch: Arch, max_steps: int) -> Blocks:
    """Output-stationary mapping: K/P/Q across banks, C/R/S/K across
    columns, the rest in time at the bank (overflow to the innermost)."""
    t, nl = arch.target, len(arch.levels)
    slots = {(li, sp): {d: 1 for d in DIMS}
             for li in range(nl) for sp in (False, True)}
    rem = {d: layer[d] for d in DIMS}

    def greedy(li, dims, cap):
        used = 1
        for d in dims:
            best = max([f for f in _divisors(rem[d]) if used * f <= cap]
                       or [1])
            slots[(li, True)][d] = best
            used *= best
            rem[d] //= best

    for li in range(t):
        greedy(li, ("P", "Q", "K"), arch.fanout(li + 1))
    greedy(t, ("C", "R", "S", "K"),
           arch.fanout(t + 1) if t + 1 < nl else 1)
    n_steps = math.prod(rem.values())
    for d in ("C", "R", "S", "K", "Q", "P", "N"):
        while n_steps > max_steps and rem[d] > 1:
            small = _divisors(rem[d])[1]
            slots[(nl - 1, False)][d] *= small
            rem[d] //= small
            n_steps //= small
    for d in DIMS:
        slots[(t, False)][d] = rem[d]
    out = []
    for li in range(nl):
        temporal = sorted([(d, f, False) for d, f in
                           slots[(li, False)].items() if f > 1],
                          key=lambda lp: _STREAM_GROUP[lp[0]])
        spatial = [] if li == nl - 1 else [
            (d, f, True) for d, f in slots[(li, True)].items() if f > 1]
        out.append(tuple(temporal + spatial))
    return tuple(out)


def random_blocks(layer: Dict, arch: Arch, rng: random.Random,
                  max_steps: int) -> Blocks:
    """One seeded draw: random spatial splits under the fanouts, random
    temporal factors, half the draws in stream order; 64 tries, then the
    heuristic mapping."""
    t, nl = arch.target, len(arch.levels)
    for _ in range(64):
        per_slot = {}
        for li in range(nl):
            per_slot[(li, False)] = {}
            if li < nl - 1:
                per_slot[(li, True)] = {}
        for d in DIMS:
            rem = layer[d]
            for li in range(nl - 1):
                cap = arch.fanout(li + 1)
                f = 1
                if not (d in RED and li != t) and rng.random() < 0.5:
                    f = rng.choice([x for x in _divisors(rem) if x <= cap])
                per_slot[(li, True)][d] = f
                rem //= f
            for li in range(nl):
                f = rem if li == nl - 1 else rng.choice(_divisors(rem))
                per_slot[(li, False)][d] = f
                rem //= f
        for li in range(nl - 1):
            sl = per_slot[(li, True)]
            while math.prod(sl.values()) > arch.fanout(li + 1):
                big = sorted(sl, key=lambda d: -sl[d])[0]
                per_slot[(li, False)][big] *= sl[big]
                sl[big] = 1
        steps = math.prod(math.prod(per_slot[(li, False)].values())
                          for li in range(t + 1))
        if steps > max_steps:
            continue
        stream = rng.random() < 0.5
        blocks = _blocks(arch, per_slot, rng, stream)
        if _valid(layer, arch, blocks):
            return blocks
    return heuristic(layer, arch, 65536)


def candidates(layer: Dict, arch: Arch, seed: int, salt: int,
               n: int, max_steps: int) -> List[Blocks]:
    rng = random.Random((seed << 20) ^ salt)
    out = [heuristic(layer, arch, max_steps)]
    for _ in range(n - 1):
        b = random_blocks(layer, arch, rng, max_steps)
        if b not in out:
            out.append(b)
    return out


# ---------------------------------------------------------------------------
# Coordinate maps: consumer tile -> producer output box (+ ready-at-0).
# ---------------------------------------------------------------------------

def project(cmap: Dict, prod: Dict, cons: Dict, lo, hi):
    kind = cmap["kind"]
    z = np.zeros_like(lo["P"])
    never = np.zeros(lo["P"].shape, dtype=bool)
    if kind == "identity":
        st, pad, pool = cons["stride"], cons["pad"], cmap["pool"]
        h0 = (lo["P"] * st - pad + lo["R"]) * pool
        h1 = ((hi["P"] - 1) * st - pad + hi["R"] - 1) * pool + pool - 1
        w0 = (lo["Q"] * st - pad + lo["S"]) * pool
        w1 = ((hi["Q"] - 1) * st - pad + hi["S"] - 1) * pool + pool - 1
        ready0 = (h1 < 0) | (w1 < 0) | (h0 >= prod["P"]) | (w0 >= prod["Q"])
        return ({"K": lo["C"], "P": h0, "Q": w0},
                {"K": hi["C"], "P": h1 + 1, "Q": w1 + 1}, ready0)
    if kind == "full":
        return ({"K": z, "P": z, "Q": z},
                {"K": z + prod["K"], "P": z + prod["P"], "Q": z + prod["Q"]},
                never)
    seq, hd = cmap["seq"], cmap["hd"]
    if kind == "headfold":          # rows h*seq+m of the consumer
        r0, r1 = lo["P"], hi["P"] - 1
        g0, g1 = r0 // seq, r1 // seq
        span = g1 > g0
        return ({"K": g0 * hd + lo["C"], "P": np.where(span, 0, r0 % seq),
                 "Q": z},
                {"K": g1 * hd + hi["C"],
                 "P": np.where(span, seq - 1, r1 % seq) + 1, "Q": z + 1},
                never)
    if kind == "headunfold":        # consumer column c = h*hd + j
        c0, c1 = lo["C"], hi["C"] - 1
        g0, g1 = c0 // hd, c1 // hd
        span = g1 > g0
        return ({"K": np.where(span, 0, c0 % hd), "P": g0 * seq + lo["P"],
                 "Q": z},
                {"K": np.where(span, hd - 1, c1 % hd) + 1,
                 "P": g1 * seq + hi["P"], "Q": z + 1},
                never)
    if kind in ("qk_weight", "av_weight"):
        g0 = (lo["P"] // seq) // cmap["group"]
        g1 = ((hi["P"] - 1) // seq) // cmap["group"]
        if kind == "qk_weight":
            return ({"K": g0 * hd + lo["C"], "P": lo["K"], "Q": z},
                    {"K": g1 * hd + hi["C"], "P": hi["K"], "Q": z + 1},
                    never)
        return ({"K": g0 * hd + lo["K"], "P": lo["C"], "Q": z},
                {"K": g1 * hd + hi["K"], "P": hi["C"], "Q": z + 1}, never)
    raise ValueError(f"unknown coordinate map {kind!r}")


def _sparse_table(a: np.ndarray, axis: int) -> List[np.ndarray]:
    levels = [a]
    w = 1
    while 2 * w <= a.shape[axis]:
        prev = levels[-1]
        n = prev.shape[axis] - w
        levels.append(np.maximum(prev.take(np.arange(n), axis=axis),
                                 prev.take(np.arange(w, w + n), axis=axis)))
        w *= 2
    return levels


def _range_max(table, rows, lo, hi):
    """max of table-level-0[rows[i], lo[i]:hi[i], ...] for every i."""
    length = hi - lo
    lev = np.floor(np.log2(np.maximum(length, 1))).astype(np.int64)
    out = None
    for j in np.unique(lev):
        sel = lev == j
        t = table[j]
        v = np.maximum(t[rows[sel], lo[sel]],
                       t[rows[sel], hi[sel] - (1 << j)])
        if out is None:
            out = np.empty((len(lo),) + v.shape[1:], dtype=v.dtype)
        out[sel] = v
    return out


def _unique_rows(cols: Sequence[np.ndarray], bases: Sequence[int]):
    """Distinct rows of small non-negative int columns (each below its
    base): the distinct rows as columns, and each row's index among them."""
    key = np.zeros(cols[0].shape, dtype=np.int64)
    for c, b in zip(cols, bases):
        key = key * b + c
    uk, inv = np.unique(key, return_inverse=True)
    out = []
    for b in reversed(bases):
        out.append(uk % b)
        uk = uk // b
    return out[::-1], inv.ravel()


def box_max(f: np.ndarray, lo: Dict, hi: Dict, table=None) -> np.ndarray:
    """Maximum of ``f[K, P, Q]`` over every box ``[lo, hi)``; ``table`` is
    ``_sparse_table(f, 0)`` when the caller keeps it."""
    nk, np_, nq = (s + 1 for s in f.shape)
    (k0, k1), ik = _unique_rows([lo["K"], hi["K"]], [nk, nk])
    table = table if table is not None else _sparse_table(f, 0)
    g = _range_max([x[None] for x in table],
                   np.zeros(len(k0), np.int64), k0, k1)
    (gi, p0, p1), ip = _unique_rows([ik, lo["P"], hi["P"]],
                                    [len(k0), np_, np_])
    h = _range_max(_sparse_table(g, 1), gi, p0, p1)
    (hi_, q0, q1), iq = _unique_rows([ip, lo["Q"], hi["Q"]],
                                     [len(gi), nq, nq])
    m = _range_max(_sparse_table(h, 1), hi_, q0, q1)
    return m[iq]


# ---------------------------------------------------------------------------
# Scheduling.
# ---------------------------------------------------------------------------

def transform(ready: np.ndarray, p: Dict):
    """Tiles in ascending ready order, dealt round-robin over the banks;
    a re-homed tile waits one tile move. Returns (end, finish by original
    (bank, step), number moved)."""
    nb, nt = ready.shape
    flat = ready.reshape(-1)
    order = np.argsort(flat, kind="stable")
    pos = np.arange(flat.size)
    moved = (pos % nb) != (order // nt)
    eff = flat[order] + moved.astype(flat.dtype) * p["tile_move"]
    r = eff.reshape(nt, nb).T                        # new bank x slot
    step = p["step"]
    s = np.arange(nt, dtype=flat.dtype)
    fin = np.maximum.accumulate(r - s[None, :] * step, axis=1) \
        + (s[None, :] + 1) * step
    out = np.empty_like(flat)
    out[order] = fin.T.reshape(-1)
    return fin.max(), out.reshape(nb, nt), int(moved.sum())


class Network:
    """Layers (dicts of the 7 dims, stride, pad) and per-layer edges
    (``{"producer": j, "map": {...}}``)."""

    def __init__(self, layers: Sequence[Dict], edges: Sequence[Sequence[Dict]]):
        self.layers = [dict(l) for l in layers]
        self.edges = [list(e) for e in edges]
        self.has_consumer = [False] * len(layers)
        for es in self.edges:
            for e in es:
                self.has_consumer[e["producer"]] = True


class _Done:
    def __init__(self, mi, perf, end, finish):
        self.mi, self.perf, self.end = mi, perf, end
        self.fin_step = finish.max(axis=0)
        self._f = None
        self._table = None

    @property
    def f(self):
        if self._f is None:
            self._f = self.mi.finish_steps()
        return self._f

    @property
    def table(self):
        if self._table is None:
            self._table = _sparse_table(self.f, 0)
        return self._table


def _tail(mi: MapInfo, f: np.ndarray) -> float:
    L = mi.layer
    ps = np.repeat(np.linspace(0, L["P"] - 1, 5).astype(np.int64), 5)
    qs = np.tile(np.linspace(0, L["Q"] - 1, 5).astype(np.int64), 5)
    return float(f[L["K"] - 1, ps, qs].mean() + 1) / mi.n_steps


def _ready(net: Network, i: int, mi: MapInfo, done: Dict[int, _Done], dt):
    lo, hi = mi.tiles()
    ready = np.zeros((mi.n_banks, mi.n_steps), dtype=dt)
    cons = net.layers[i]
    for e in net.edges[i]:
        pd = done[e["producer"]]
        prod = net.layers[e["producer"]]
        plo, phi, r0 = project(e["map"], prod, cons, lo, hi)
        plo = {d: np.clip(plo[d], 0, prod[d] - 1).ravel() for d in OUT}
        phi = {d: np.clip(phi[d], 1, prod[d]).ravel() for d in OUT}
        empty = np.zeros(plo["K"].shape, dtype=bool)
        for d in OUT:
            empty |= phi[d] <= plo[d]
            phi[d] = np.maximum(phi[d], plo[d] + 1)
        step = box_max(pd.f, plo, phi, pd.table).reshape(ready.shape)
        r = pd.fin_step[step] + pd.perf["tile_move"]
        r = np.where(r0 | empty.reshape(ready.shape), dt.type(0), r)
        ready = np.maximum(ready, r.astype(dt))
    return ready


def _objective(obj: str, lat, energy):
    if obj == "latency":
        return lat
    if obj == "edp":
        return lat * energy
    if obj == "energy":
        return energy
    raise ValueError(f"objective {obj!r} has no reference")


def _evaluate(net, i, mi, done, dt, objective):
    """(forward score, committed layer, layer energy) of one mapping."""
    p = layer_perf(mi, dt)
    f = mi.finish_steps()
    tail = _tail(mi, f) if net.has_consumer[i] else 0.0
    penalty = dt.type(tail) * p["compute"]
    if not net.edges[i]:
        s = np.arange(mi.n_steps, dtype=dt)
        fin = np.broadcast_to((s + 1) * p["step"], (mi.n_banks, mi.n_steps))
        end = p["compute"] + p["out_move"]
        score = _objective(objective, end + penalty, p["energy"])
        d = _Done(mi, p, end, fin)
        d._f = f
        return score, d, p["energy"]
    ready = _ready(net, i, mi, done, dt)
    tend, fin, n_moved = transform(ready, p)
    move_e = dt.type(n_moved) * p["tile_bytes"] * p["move_pj_per_byte"]
    end = tend + p["out_move"]
    score = _objective(objective, end + penalty, p["energy"] + move_e)
    d = _Done(mi, p, end, fin)
    d._f = f
    return score, d, p["energy"] + move_e


def search(net: Network, arch: Dict, params: Dict,
           chosen: Optional[Sequence[Blocks]] = None,
           check_layers: Optional[set] = None,
           dtype=np.float64) -> Dict:
    """Forward-strategy transform-mode search over ``net``.

    Without ``chosen`` the reference searches itself and commits its own
    best candidate per layer. With ``chosen`` (the answer under judgement)
    it commits the given mapping of each layer and, for layers in
    ``check_layers``, scores the whole candidate pool to judge the choice.
    Returns the chosen blocks, per-layer end times, the total, the energy,
    the number of chosen mappings outside the candidate pool, and the
    largest relative excess of a judged choice over the best candidate."""
    a = Arch(arch)
    dt = np.dtype(dtype)
    done: Dict[int, _Done] = {}
    picks, ends = [], []
    energy = dt.type(0)
    off_pool, excess = 0, 0.0
    for i, layer in enumerate(net.layers):
        pool = candidates(layer, a, params["seed"], i,
                          params["n_candidates"], params["max_steps"])
        if chosen is None or (check_layers is not None and i in check_layers):
            scored = [_evaluate(net, i, MapInfo(layer, a, b), done, dt,
                                params["objective"]) for b in pool]
            best = min(range(len(pool)), key=lambda k: scored[k][0])
        if chosen is None:
            pick = pool[best]
            _, d, e = scored[best]
        else:
            pick = tuple(tuple(tuple(lp) for lp in blk) for blk in chosen[i])
            if pick not in pool:
                off_pool += 1
            if check_layers is not None and i in check_layers \
                    and pick in pool:
                s_pick = scored[pool.index(pick)][0]
                s_best = scored[best][0]
                excess = max(excess, float((s_pick - s_best) / s_best))
                _, d, e = scored[pool.index(pick)]
            else:
                _, d, e = _evaluate(net, i, MapInfo(layer, a, pick), done,
                                    dt, params["objective"])
        done[i] = d
        picks.append(pick)
        ends.append(float(d.end))
        energy = energy + e
    return {"chosen": picks, "ends": ends, "total": max(ends),
            "energy": float(energy), "off_pool": off_pool,
            "choice_excess": excess}
