"""The DeepSeek-V2 deployment and the reader of the generic-map ready
stage, on the CPU: the deployment file against the program's zoo entry,
the plain reference against the program on a latent-attention network
with an expert share, and the reader on hand-built runs."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, program, reference  # noqa: E402

CELL = "deepseek_v2.decode32k.search-c8"


def _deployment():
    w, c = harness.cell(harness.load_manifest(), CELL)
    with open(os.path.join(harness.ROOT, c["file"])) as fh:
        return json.load(fh)


def test_deployment_file_is_the_zoo_entry_cut_by_depth_and_experts():
    from repro.configs import get_config
    dep = _deployment()
    cfg = get_config("deepseek_v2")
    assert program.network_name(dep) == "deepseek_v2_ep8:decode@32768x5"
    assert (dep["hidden_size"], dep["num_attention_heads"],
            dep["q_lora_rank"], dep["kv_lora_rank"],
            dep["qk_nope_head_dim"], dep["qk_rope_head_dim"],
            dep["v_head_dim"], dep["intermediate_size"],
            dep["moe_intermediate_size"], dep["num_experts_per_tok"],
            dep["n_shared_experts"], dep["n_group"], dep["topk_group"],
            dep["first_k_dense_replace"]) == \
        (cfg.d_model, cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
         cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
         cfg.d_ff_dense, cfg.d_ff, cfg.top_k, cfg.n_shared_experts,
         cfg.n_expert_groups, cfg.topk_groups, cfg.n_dense_layers)
    assert dep["published_n_routed_experts"] == cfg.n_experts
    assert dep["n_routed_experts"] * dep["n_group"] == cfg.n_experts
    assert dep["published_num_hidden_layers"] == cfg.n_layers
    assert sorted(dep["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers"]
    desc = program.Deployment(dep).desc
    assert len(desc.layers) == 14 + 4 * 78
    routed = [l for l in desc.layers if ".exp" in l.name]
    assert len(routed) == 4 * dep["n_routed_experts"] * 3


def test_cell_reports_the_search_metrics():
    m = harness.load_manifest()
    names = {x["name"] for x in harness.metrics_for(m, CELL, True)}
    assert "ready_cmap_share.search" in names
    assert len(names) == 7
    assert {x["name"] for x in harness.metrics_for(m, CELL, False)} == \
        {"search_s", "setup_s"}


def test_reference_agrees_on_latent_attention_with_an_expert_share():
    with open(os.path.join(harness.HERE, "configs",
                           "resnet18-dram_pim.json")) as fh:
        arch = json.load(fh)["arch"]
    cfg = {"scenario": {"arch": "deepseek_v2_smoke_ep2", "phase": "decode",
                        "length": 64},
           "num_hidden_layers": 2, "arch": arch, "mode": "transform",
           "strategy": "forward", "objective": "latency"}
    dep = program.Deployment(cfg)
    plain = dep.plain_network()
    kinds = {e["map"]["kind"] for es in plain["edges"] for e in es}
    assert {"headfold", "headunfold", "full", "identity"} <= kinds
    rnet = reference.Network(plain["layers"], plain["edges"])
    params = {"seed": 5, "n_candidates": 4, "max_steps": 256,
              "objective": "latency"}
    ans = dep.search(dep.search_config(4, 256, 5))
    ref = reference.search(rnet, arch, params, chosen=ans["chosen"],
                           check_layers=set(range(len(rnet.layers))))
    assert ref["off_pool"] == 0 and ref["choice_excess"] == 0.0
    assert abs(ans["total"] - ref["total"]) <= 1e-12 * ref["total"]
    assert abs(ans["energy"] - ref["energy"]) <= 1e-12 * ref["energy"]
    ctl = reference.search(rnet, arch, params, dtype=np.float32)
    assert max(abs(ctl["total"] - ref["total"]) / ref["total"],
               abs(ctl["energy"] - ref["energy"]) / ref["energy"]) > 1e-10


def test_ready_cmap_share_reader():
    read = harness.reader("ready_cmap_share.search")
    run = {"window_s": 10.0,
           "counters": {"engine.ready_cmap_s": 0.5,
                        "engine.score_dense_s": 3.0}}
    assert read(run) == pytest.approx(5.0)
    # a program without the counter (older ones) reports nothing
    assert read({"window_s": 10.0,
                 "counters": {"engine.score_dense_s": 3.0}}) is None
    assert read({"window_s": 10.0}) is None
