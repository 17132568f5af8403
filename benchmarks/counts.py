"""Operations and bytes one training micro-step requires, from its shapes
alone (never from XLA's ``cost_analysis``), for ``step_mfu`` and
``agg_hbm_roofline``, by the rules of the configuration's family
(``family.py``). Both are lower bounds of what any implementation must do, so
a share computed from them cannot pass 100% and does not move when a later
PR replaces the implementation.

``shapes``: graphs G in the micro-batch, real nodes N and edges E per graph,
hidden H, layers L, virtual channels C, node features F, node attributes A,
edge attributes D, ``dtype_bytes`` of the message MLPs' activations, and
``model_name`` (FastEGNN where absent: these entry points serve the metric
readers, which hold shapes alone).
"""

from __future__ import annotations

from benchmarks import family


def forward_matmul_flops(s: dict) -> float:
    return family.of(s, default="FastEGNN").forward_matmul_flops(s)


def step_flops(s: dict) -> float:
    """Forward and backward of one micro-step: a Dense's backward is two
    matmuls of the forward's size. Rematerialized recompute does not count."""
    return 3.0 * forward_matmul_flops(s)


def agg_bytes(s: dict) -> float:
    return family.of(s, default="FastEGNN").agg_bytes(s)


def _move(s: dict):
    """Bytes of one gather of width ``width_bytes`` (its N source rows, an
    int32 index per edge, E rows written), or of a segment sum, its mirror."""
    return lambda width_bytes: s["edges"] * width_bytes + s["edges"] * 4 + s["nodes"] * width_bytes


def fastegnn_forward_matmul_flops(s: dict) -> float:
    """Multiply-adds x2 of every Dense in one forward pass, in the cheapest
    algebraic order the model admits (a first Dense over a concatenation of
    gathered or broadcast node rows is applied before the gather/broadcast).
    Elementwise work, reductions and the tiny MMD term are not counted."""
    G, N, E = s["graphs"], s["nodes"], s["edges"]
    H, L, C = s["hidden_nf"], s["n_layers"], s["virtual_channels"]
    F, A, D = s["node_feat_nf"], s["node_attr_nf"], s["edge_attr_nf"]
    layer = (
        2 * N * H * H * 2 + 2 * E * (1 + D) * H      # phi_e first Dense (h parts on nodes)
        + 2 * E * H * H                              # phi_e second Dense
        + 2 * E * H * H + 2 * E * H                  # phi_x
        + 2 * N * H * H + 2 * C * H * H + 2 * N * C * (1 + C) * H   # phi_ev first Dense
        + 2 * N * C * H * H                          # phi_ev second Dense
        + 2 * (2 * N * C * H * H + 2 * N * C * H)    # phi_xv, phi_X
        + 2 * N * H * H + 2 * N * H                  # phi_v
        + 2 * N * (3 * H + A) * H + 2 * N * H * H    # phi_h
        + 2 * C * 2 * H * H + 2 * C * H * H          # phi_hv
    )
    return float(G * (2 * N * F * H + L * layer))


def fastegnn_agg_bytes(s: dict) -> float:
    """Least HBM bytes the gathers and segment sums of one micro-step move,
    forward and transpose. A gather of width w reads its N source rows and an
    int32 index per edge and writes E rows; a segment sum is the mirror image.
    Per layer: gathers of x at both edge ends (3 float32), of the two hoisted
    phi_e products (H values of the MLPs' dtype); segment sums of the
    translations (3 float32) and of the messages (H float32). The transpose
    of each moves the same bytes."""
    G, H, L, b = s["graphs"], s["hidden_nf"], s["n_layers"], s["dtype_bytes"]
    move = _move(s)
    layer = 2 * move(3 * 4) + 2 * move(H * b) + move(3 * 4) + move(H * 4)
    return float(G * L * 2 * layer)


def fasttfn_forward_matmul_flops(s: dict) -> float:
    """As FastEGNN's, of FastTFN's Denses: ``phi_e``, the virtual-node MLPs
    and ``phi_h``/``phi_hv`` as there (no ``phi_x``, no ``phi_v``), and per
    layer the TFN's two radial nets on every edge (1 -> M -> M -> 1 and
    1 -> M -> M -> 3, M = ``weights.RADIAL_MID``) and its 1 x 1
    self-interaction on the three components of each node's velocity (applied
    on the node, before the edge gathers it). The spherical-harmonic basis,
    the layer norms and the kernel contraction are elementwise and not
    counted, nor the MMD term."""
    from benchmarks.weights import RADIAL_MID as M

    G, N, E = s["graphs"], s["nodes"], s["edges"]
    H, L, C = s["hidden_nf"], s["n_layers"], s["virtual_channels"]
    F, A, D = s["node_feat_nf"], s["node_attr_nf"], s["edge_attr_nf"]
    layer = (
        2 * N * H * H * 2 + 2 * E * (1 + D) * H      # phi_e first Dense (h parts on nodes)
        + 2 * E * H * H                              # phi_e second Dense
        + 2 * N * H * H + 2 * C * H * H + 2 * N * C * (1 + C) * H   # phi_ev first Dense
        + 2 * N * C * H * H                          # phi_ev second Dense
        + 2 * (2 * N * C * H * H + 2 * N * C * H)    # phi_xv, phi_X
        + 2 * N * (3 * H + A) * H + 2 * N * H * H    # phi_h
        + 2 * C * 2 * H * H + 2 * C * H * H          # phi_hv
        + 2 * E * (M + M * M + M * 1)                # radial net 0 -> 1
        + 2 * E * (M + M * M + M * 3)                # radial net 1 -> 1
        + 2 * N * 3                                  # self-interaction
    )
    return float(G * (2 * N * F * H + L * layer))


def fasttfn_agg_bytes(s: dict) -> float:
    """As FastEGNN's, of what FastTFN's layer gathers and sums: x at both
    edge ends (3 float32; r_ij feeds ``phi_e`` and the TFN alike), the two
    hoisted ``phi_e`` products (H values of the MLPs' dtype); segment sums of
    the TFN's messages (3 float32) and of ``phi_e``'s (H float32); each with
    its transpose, which moves the same bytes. The TFN's sources at the
    sending end, charge and velocity (4 float32), are gathered once: they are
    the data, no gradient flows back to them. The self-interaction is a term
    of the receiving node itself and gathers nothing."""
    G, H, L, b = s["graphs"], s["hidden_nf"], s["n_layers"], s["dtype_bytes"]
    move = _move(s)
    layer = 2 * move(3 * 4) + 2 * move(H * b) + move(3 * 4) + move(H * 4)
    return float(G * L * (2 * layer + move(4 * 4)))
