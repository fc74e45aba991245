import json
import os

import pytest

from conftest import BENCH

import seq_cost


def model(**over):
    with open(os.path.join(BENCH, "configs", "glm47flash-ep8.json")) as f:
        config = json.load(f)
    m = {k: v for k, v in config.items() if isinstance(v, (int, float))}
    m.update(router_width=config["deployment"]["router_width"],
             experts_first=config["deployment"]["experts_first"])
    m.update(over)
    return config, m


def test_the_configurations_parameter_count_is_the_issues():
    config, m = model()
    assert seq_cost.n_parameters(m) == config["deployment"]["parameters_here"]
    assert round(seq_cost.n_parameters(m) * 16 / 1e9, 1) == 11.3


def test_the_cells_step_is_59_tflop_and_the_shares_add_up():
    _config, m = model()
    cost = seq_cost.seq_cost(m, 2, 8192, 8, pairs=8 * 5 * 8192.0)
    assert cost["flops"] / 8 == pytest.approx(59.4e12, rel=0.01)
    assert cost["forward_flops_per_event"] == pytest.approx(1.21e9, rel=0.01)
    assert sum(cost["share"].values()) == pytest.approx(1.0)
    # MLA is 63% of it, the causal scores two thirds of that
    mla = cost["share"]["mla_proj"] + cost["share"]["attn"]
    assert mla == pytest.approx(0.63, abs=0.01)
    assert cost["share"]["attn"] / mla == pytest.approx(2 / 3, abs=0.01)


def test_the_causal_scores_are_the_lower_triangle():
    _config, m = model()
    # a second row doubles the attention; a doubled row length quadruples
    # the triangle but for its diagonal
    one = seq_cost.seq_cost(m, 1, 1024, 1, pairs=0.0)["attn"]["flops"]
    two = seq_cost.seq_cost(m, 1, 2048, 1, pairs=0.0)["attn"]["flops"]
    assert two / one == pytest.approx(2048 * 2049 / (1024 * 1025))
    heads, width, layers = 20, 256 + 256, 6
    assert one == 3 * 2 * (1024 * 1025 // 2) * layers * heads * width


def test_the_experts_cost_follows_the_routed_pairs():
    _config, m = model()
    a = seq_cost.seq_cost(m, 2, 8192, 8, pairs=1000.0)["experts"]
    b = seq_cost.seq_cost(m, 2, 8192, 8, pairs=2000.0)["experts"]
    assert b["flops"] == 2 * a["flops"] == 2 * 1000 * 3 * 2 * 3 * 2048 * 1536
    assert b["bytes"] > a["bytes"] > 0
