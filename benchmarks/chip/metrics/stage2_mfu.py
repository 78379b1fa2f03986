"""stage2_mfu: the FLOPs that the policy's forward and backward passes
require per episode, times the episodes per second of the traced window,
over chips x the chip's peak, in %.

The count comes from shapes alone (``policy_flops_per_episode``): the
encoder and the static heads once per update, shared by the batch, and
the PLC head at every step of every episode, each matmul 2 x in x out
FLOPs per row; backward is twice forward.  It leaves out the WC oracle,
the SEL softmax and the one-hot grid that the encoder's kernel multiplies
today.  The policy computes in float32; the peak is the chip's bf16 one,
the only dense peak the table has.
"""


def mlp_flops(rows, dims):
    return rows * sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def policy_flops_per_episode(s: dict) -> float:
    p = s["policy"]
    n, m, nd, K = s["n"], s["m"], s["nd"], s["batch"]
    dh, dz, dy = p["d_hidden"], p["d_z"], p["d_y"]
    fs, fd, fe = p["static_features"], p["device_features"], \
        p["edge_features"]
    enc = mlp_flops(n, [fs, dh])
    for _ in range(p["gnn_layers"]):
        enc += 2 * mlp_flops(m, [2 * dh + fe, dh, dh])
        enc += mlp_flops(n, [3 * dh, dh, dh])
    enc += 2 * mlp_flops(n, [fs, dz]) + mlp_flops(n, [3 * dh + dz, dh, 1])
    step = (mlp_flops(nd, [fd, dy]) + mlp_flops(nd, [2 * dh + dy + dz, dh])
            + mlp_flops(nd, [dh, 1]))
    return 3.0 * (enc / K + n * step)


def read(ctx):
    if ctx.get("kind") != "stage2" or not ctx.get("window_s"):
        return None
    rate = ctx["episodes"] / ctx["window_s"]
    peak = ctx["chips"] * ctx["peaks"]["flops_per_s"]
    return 100.0 * policy_flops_per_episode(ctx["shapes"]) * rate / peak
