"""The FLOP and byte counts against a hand count at the paper's widths
(`M4Config` defaults: hidden 400, GNN 300, MLP 200, 3 rounds, 64/128
slots, 9-wide config input)."""
from bench import flops

PAPER = {"hidden": 400, "gnn_dim": 300, "mlp_hidden": 200, "gnn_layers": 3,
         "snap_flows": 64, "snap_links": 128, "max_path": 8, "cfg_dim": 9}


def test_gru_cell_by_hand():
    # GRU-1: 64 rows, input 1 + 3 + 9 = 13, hidden 400, gates 1200
    # 2·64·13·1200 + 2·64·400·1200 + 12·64·400 = 1,996,800 + 61,440,000
    #   + 307,200
    # bytes: x (64·13), h (64·400) read, h (64·400) written, float32;
    # the weights stay on chip through the scan
    assert flops.gru_cell(64, 13, 400) == (63_744_000, 208_128)


def test_bipartite_round_by_hand():
    # 2·64·128·300 twice (M·l, Mᵀ·f) + 2·64·600·300 + 2·128·600·300
    #   + 2·(64 + 128)·300 relu/bias = 9,830,400 + 23,040,000 + 46,080,000
    #   + 115,200
    # bytes: f, l, M read and f, l written:
    # (2·64·300 + 2·128·300 + 64·128) · 4
    assert flops.bipartite_round(64, 128, 300) == (79_065_600, 493_568)


def test_m4_event_by_hand():
    parts = flops.m4_event(PAPER)
    # GRU matmuls 516,403,200 (GRU-1, GRU-A, GRU-2, GRU-B) + gate
    # arithmetic 12·400·(64 + 128 + 64 + 128) = 1,843,200
    assert parts["gru"] == 516_403_200 + 1_843_200
    assert parts["gnn"] == 3 * 79_065_600                  # 237,196,800
    assert parts["projections"] == 2 * 64 * 400 * 300 + 2 * 128 * 400 * 300
    # slowdown head: 64 rows of 410 -> 200 -> 200 -> 1
    assert parts["sldn_head"] == 2 * 64 * (410 * 200 + 200 * 200 + 200)
    assert parts["flow_init"] == 2 * (12 * 200 + 200 * 400)
    assert flops.m4_event_flops(PAPER) == 817_329_600      # ~0.82 GFLOP


def test_kernel_calls_per_step():
    calls = flops.m4_kernel_calls(PAPER)
    assert len(calls["fused_gru"]) == 4
    assert len(calls["bipartite_round"]) == 3
    assert sum(f for f, _ in calls["fused_gru"]) == 516_403_200 + 1_843_200
    # GRU-A: 128 link rows, input 1 + 1 + 9 = 11
    assert calls["fused_gru"][1] == flops.gru_cell(128, 11, 400)

