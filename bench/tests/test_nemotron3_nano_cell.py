"""The Nemotron-3-Nano deployment and the reader of the mixed-edge dense
scoring stage, on the CPU: the deployment file against the program's zoo
entry, the plain reference against the program on a layer-pattern
network with an expert share, and the reader on hand-built runs."""
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, program, reference  # noqa: E402

CELL = "nemotron3_nano.decode32k.search-c8"
METRIC = "score_mixed_share.search"


def _deployment():
    w, c = harness.cell(harness.load_manifest(), CELL)
    with open(os.path.join(harness.ROOT, c["file"])) as fh:
        return json.load(fh)


def test_deployment_file_is_the_zoo_entry_cut_by_depth_pattern_and_experts():
    from repro.configs import get_config
    dep = _deployment()
    cfg = get_config("nemotron_3_nano")
    assert program.network_name(dep) == \
        "nemotron_3_nano_ep16:decode@32768x7"
    assert (dep["hidden_size"], dep["num_attention_heads"],
            dep["num_key_value_heads"], dep["head_dim"],
            dep["vocab_size"], dep["mamba_num_heads"],
            dep["mamba_head_dim"], dep["ssm_state_size"], dep["n_groups"],
            dep["chunk_size"], dep["conv_kernel"], dep["expand"],
            dep["moe_intermediate_size"],
            dep["moe_shared_expert_intermediate_size"],
            dep["num_experts_per_tok"], dep["n_shared_experts"],
            dep["mlp_hidden_act"], dep["max_position_embeddings"]) == \
        (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
         cfg.vocab, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state,
         cfg.ssm_groups, cfg.ssm_chunk, cfg.ssm_conv, cfg.ssm_expand,
         cfg.d_ff, cfg.d_ff_shared, cfg.top_k, cfg.n_shared_experts,
         cfg.mlp, cfg.max_seq)
    assert dep["published_hybrid_override_pattern"] == cfg.layer_pattern
    assert cfg.layer_pattern.startswith(dep["hybrid_override_pattern"])
    assert len(dep["hybrid_override_pattern"]) == \
        dep["num_hidden_layers"] == 7
    assert dep["published_num_hidden_layers"] == cfg.n_layers
    assert dep["published_n_routed_experts"] == cfg.n_experts
    assert dep["n_routed_experts"] * 16 == cfg.n_experts
    assert sorted(dep["reduced"]) == ["hybrid_override_pattern",
                                      "n_routed_experts",
                                      "num_hidden_layers"]
    desc = program.Deployment(dep).desc
    assert len(desc.layers) == 87
    assert sum(l.macs for l in desc.layers) == 711_467_008
    routed = [l for l in desc.layers if ".exp" in l.name]
    assert len(routed) == 3 * dep["n_routed_experts"] * 2


def test_cell_reports_the_search_metrics():
    m = harness.load_manifest()
    names = {x["name"] for x in harness.metrics_for(m, CELL, True)}
    assert {METRIC, "score_full_share.search", "ready_cmap_share.search",
            "score_dense_share.search"} <= names
    assert len(names) == 9
    assert {x["name"] for x in harness.metrics_for(m, CELL, False)} == \
        {"search_s", "setup_s"}
    mixed = next(x for x in m["per_layer"] if x["name"] == METRIC)
    assert mixed["workloads"] == [CELL]


def test_reference_agrees_on_a_layer_pattern_with_an_expert_share():
    with open(os.path.join(harness.HERE, "configs",
                           "resnet18-dram_pim.json")) as fh:
        arch = json.load(fh)["arch"]
    cfg = {"scenario": {"arch": "nemotron_3_nano_smoke_ep4",
                        "phase": "decode", "length": 64},
           "num_hidden_layers": 7, "arch": arch, "mode": "transform",
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


@pytest.mark.parametrize("run,want", [
    ({"window_s": 10.0, "counters": {"engine.score_mixed_s": 0.5,
                                     "engine.score_dense_s": 3.0}}, 5.0),
    ({"window_s": 4.0, "counters": {"engine.score_mixed_s": 0.0}}, 0.0),
])
def test_reader_reads_the_counter_over_the_window(run, want):
    assert harness.reader(METRIC)(run) == pytest.approx(want)


@pytest.mark.parametrize("run", [
    {"window_s": 10.0, "counters": {"engine.score_dense_s": 3.0}},
    {"window_s": 10.0},
])
def test_reader_is_silent_without_the_counter(run):
    """A program without the counter (the parent's) reports nothing."""
    assert harness.reader(METRIC)(run) is None
