"""The benchmark's one adapter to the system under test (``repro``).

Everything the harness takes from the program goes through here: the
network a deployment names, the architecture factory, the search entry
point and its counters, the DSE sweep and the mapping service. Results
leave as plain data, so the reference never touches a program object.
"""
from __future__ import annotations

import dataclasses
import os
import sys
from typing import Dict, List

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.core import (FullMap, HeadFoldMap, HeadUnfoldMap,  # noqa: E402
                        IdentityMap, OverlapEngine, SearchConfig,
                        WeightMap, describe, optimize_network_engine)
from repro.core.arch import ARCH_PRESETS  # noqa: E402


def network_name(cfg: Dict) -> str:
    """The program's network or scenario string for a deployment file."""
    sc = cfg.get("scenario")
    if sc is None:
        return cfg["network"]
    return (f"{sc['arch']}:{sc['phase']}@{sc['length']}"
            f"x{cfg['num_hidden_layers']}")


def _map(cmap) -> Dict:
    t = type(cmap)
    if t is IdentityMap:
        return {"kind": "identity", "pool": cmap.pool}
    if t is FullMap:
        return {"kind": "full"}
    if t is HeadFoldMap:
        return {"kind": "headfold", "seq": cmap.seq, "hd": cmap.hd}
    if t is HeadUnfoldMap:
        return {"kind": "headunfold", "seq": cmap.seq, "hd": cmap.hd}
    if t is WeightMap:
        return {"kind": cmap.kind, "seq": cmap.seq, "hd": cmap.hd,
                "group": cmap.group}
    raise TypeError(f"no plain form for {t.__name__}")


class Deployment:
    """A deployment file realised in the program: network and arch."""

    def __init__(self, cfg: Dict):
        self.cfg = cfg
        self.desc = describe(network_name(cfg))
        self.arch = ARCH_PRESETS[cfg["arch"]["factory"]]()
        built = self.arch.to_dict()
        for k in ("levels", "target_level", "word_bits", "timing"):
            if built[k] != cfg["arch"][k]:
                raise ValueError(
                    f"the program's {cfg['arch']['factory']}() {k} "
                    f"differs from the deployment file: {built[k]!r}")

    def plain_network(self) -> Dict:
        """Layers and edges as plain data (the search's input)."""
        return {
            "layers": [dataclasses.asdict(l) for l in self.desc.layers],
            "edges": [[{"producer": e.producer, "map": _map(e.cmap)}
                       for e in es] for es in self.desc.edges],
        }

    def search_config(self, n_candidates: int, max_steps: int,
                      seed: int) -> SearchConfig:
        return SearchConfig(n_candidates=n_candidates, max_steps=max_steps,
                            seed=seed, mode=self.cfg["mode"],
                            strategy=self.cfg["strategy"],
                            objective=self.cfg["objective"])

    def search(self, scfg: SearchConfig) -> Dict:
        """One whole-network search with a fresh engine; plain answer."""
        res = optimize_network_engine(self.desc.layers, self.desc.edges,
                                      self.arch, scfg,
                                      engine=OverlapEngine())
        return {
            "seed": scfg.seed,
            "total": float(res.total_ns),
            "energy": float(res.total_energy_pj),
            "ends": [float(l.end_ns) for l in res.layers],
            "chosen": [blocks(l.mapping) for l in res.layers],
        }


def blocks(mapping) -> List:
    return [[(lp.dim, lp.size, lp.spatial) for lp in blk]
            for blk in mapping.blocks]


def dse_sweep(dep: Deployment, p: Dict, seed: int) -> Dict:
    """One ``run_dse`` sweep with a fresh engine and in-memory journal."""
    from repro.dse.explore import DSEConfig, run_dse
    from repro.dse.persist import RunJournal
    dcfg = DSEConfig(family=dep.cfg["arch"]["factory"],
                     network=network_name(dep.cfg), mode=dep.cfg["mode"],
                     strategy=dep.cfg["strategy"], explorer=p["explorer"],
                     budget=p["budget"], seed=seed,
                     n_candidates=p["n_candidates"],
                     max_steps=p["max_steps"],
                     objective=dep.cfg["objective"])
    res = run_dse(dcfg, journal=RunJournal())
    return {"seed": seed, "stats": dict(res.stats),
            "records": [{"point": r["point"], "total_ns": r["total_ns"],
                         "energy_pj": r["energy_pj"]} for r in res.records]}


class Server:
    """The mapping service behind its HTTP transport, in this process."""

    def __init__(self, p: Dict):
        from repro.serve.service import MappingService
        from repro.serve.transport import MappingHTTPServer
        self.service = MappingService(max_workers=p["max_workers"],
                                      memo_cap=p["memo_cap"],
                                      max_pending=p["max_pending"],
                                      flight_cap=p["flight_cap"])
        self.http = MappingHTTPServer(self.service, host="127.0.0.1",
                                      port=0).start()
        self.url = self.http.url

    def flight(self) -> List[Dict]:
        """Flight records, oldest first."""
        return list(reversed(self.service.flight.snapshot()))

    def close(self) -> None:
        self.http.close()
