"""Each count function against a hand count at a tiny shape."""
from __future__ import annotations

import run

mfu = run.load_file(run.HERE / "metrics" / "stage2_mfu.py")
wc = run.load_file(run.HERE / "metrics" / "wc_oracle_roofline.py")
gm = run.load_file(run.HERE / "metrics" / "gnn_mp_roofline.py")

POLICY = {"d_hidden": 2, "d_z": 1, "d_y": 1, "gnn_layers": 1,
          "static_features": 1, "device_features": 1, "edge_features": 1}


def test_policy_flops_by_hand():
    # n=3 vertices, m=2 edges, nd=2 devices, batch 4; dh=2, dz=dy=1
    s = {"n": 3, "m": 2, "nd": 2, "batch": 4, "policy": POLICY}
    embed = 3 * 2 * (1 * 2)                        # n x [1 -> 2]
    psi = 2 * 2 * 2 * (5 * 2 + 2 * 2)              # 2 dirs x m x [5,2,2]
    phi = 3 * 2 * (6 * 2 + 2 * 2)                  # n x [6,2,2]
    zs = 2 * 3 * 2 * (1 * 1)                       # sel_z, plc_z
    head = 3 * 2 * (7 * 2 + 2 * 1)                 # n x [7,2,1]
    enc = embed + psi + phi + zs + head
    step = 2 * 2 * (1 * 1 + 6 * 2 + 2 * 1)         # nd x plc_y, head1, 2
    assert mfu.policy_flops_per_episode(s) == 3.0 * (enc / 4 + 3 * step)


def test_mfu_reads_rate_over_peak():
    s = {"n": 3, "m": 2, "nd": 2, "batch": 4, "policy": POLICY}
    ctx = {"kind": "stage2", "window_s": 2.0, "episodes": 8, "chips": 1,
           "peaks": {"flops_per_s": mfu.policy_flops_per_episode(s)},
           "shapes": s}
    assert abs(mfu.read(ctx) - 400.0) < 1e-9      # 4 episodes/s x 1 x 100


def test_oracle_trip_counts_by_hand():
    # B=2 episodes, R = 2 + 4 resources, K = 3 candidate rows
    s = {"oracle_batch": 2, "oracle_R": 6, "oracle_K": 3}
    ops, nbytes = wc.trip_counts(s)
    assert ops == 2 * (5 * 6 + 6 * 3)
    assert nbytes == 2 * (3 * 6 * 4 + 3 * 4 + 4 + 4)


def test_segment_sum_counts_by_hand():
    s = {"n": 5, "m": 7, "policy": {"d_hidden": 3}}
    ops, nbytes = gm.call_counts(s)
    assert ops == 7 * 3
    assert nbytes == 7 * 3 * 4 + 5 * 3 * 4 + 7 * 4


def test_roofline_reads_kernel_events_only():
    s = {"n": 5, "m": 7, "policy": {"d_hidden": 3}}
    ops, nbytes = gm.call_counts(s)
    tr = run.load_file(run.HERE / "trace.py")
    ctx = {"kind": "stage2", "shapes": s, "pallas_calls": tr.pallas_calls,
           "peaks": {"flops_per_s": 1e30, "hbm_bytes_per_s": nbytes * 1e9},
           "events": {0: [
               ("%_segment_sum_mp.1 = f32[1,128,64]{2,1,0} custom-call("
                "%a, %b), custom_call_target=\"tpu_custom_call\"", 0, 4),
               ("%fusion.3 = f32[8]{0} fusion(%_segment_sum_mp.1)", 4, 100),
               ("%jvp_jit__segment_sum_mp__.7 = f32[1,128,64]{2,1,0} "
                "custom-call(%c)", 10, 14),
               ("%_segment_sum_mp_bwd.2 = f32[8]{0} custom-call(%d)",
                20, 30)]}}
    assert abs(gm.read(ctx) - 100.0 * 2 / 8) < 1e-9
    ctx["events"] = {0: [("%fusion = f32[8]{0} fusion(%x)", 0, 5)]}
    assert gm.read(ctx) is None
