"""``counts.py`` against FLOPs and bytes worked by hand for one 3-node graph."""

from benchmarks import counts

# 3 nodes, 6 edges (complete), H=4, L=1, C=2, F=2, A=1, D=2, bf16 messages
S = {"graphs": 1, "nodes": 3, "edges": 6, "hidden_nf": 4, "n_layers": 1,
     "virtual_channels": 2, "node_feat_nf": 2, "node_attr_nf": 1,
     "edge_attr_nf": 2, "dtype_bytes": 2}


def test_forward_flops_by_hand():
    N, E, H, C, F, A, D = 3, 6, 4, 2, 2, 1, 2
    embed = 2 * N * F * H                                     # 48
    phi_e = 2 * N * H * H * 2 + 2 * E * (1 + D) * H + 2 * E * H * H   # 192+144+192
    phi_x = 2 * E * H * H + 2 * E * H                         # 192+48
    phi_ev = 96 + 64 + 144 + 192          # h part on nodes, Hv part, [radial, m_X] part, 2nd Dense
    heads = 2 * (192 + 48)                # phi_xv and phi_X over N*C rows
    phi_v = 96 + 24
    phi_h = 2 * N * (3 * H + A) * H + 96  # 312 + 96
    phi_hv = 128 + 64
    by_hand = embed + phi_e + phi_x + phi_ev + heads + phi_v + phi_h + phi_hv
    assert embed == 48 and phi_e == 528 and phi_x == 240 and phi_h == 408
    assert counts.forward_matmul_flops(S) == by_hand == 2512
    assert counts.step_flops(S) == 3 * by_hand


def test_agg_bytes_by_hand():
    # one move of width w bytes: E*w + E*4 + N*w
    m = lambda w: 6 * w + 6 * 4 + 3 * w
    layer = 2 * m(12) + 2 * m(4 * 2) + m(12) + m(4 * 4)     # x both ends, two bf16 products, two sums
    assert m(12) == 132 and m(8) == 96 and m(16) == 168
    assert counts.agg_bytes(S) == 2 * layer == 2 * (264 + 192 + 132 + 168)


def test_scale_linearly_with_graphs_and_layers():
    big = dict(S, graphs=5, n_layers=3)
    assert counts.agg_bytes(big) == 15 * counts.agg_bytes(S)
