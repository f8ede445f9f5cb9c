"""Model FLOP utilisation of the whole round: the local-gradient pass's
model FLOPs (forward + backward, from shapes; the eval and recomputation
not counted) times the rounds of the traced window, over the window and
the chips' bf16 peak (float32 matmuls run as one bf16 pass at the default
precision)."""
LAYER = "round"
UNIT = "%"
MOVES = "rounds_per_s"


def read(trace):
    flops = trace["flops_per_round"] * trace["rounds"]
    peak = trace["chips"] * trace["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / trace["window_s"] / peak
