"""Share of its memory roofline that the Pallas ``sparsify_quantize_ef``
kernel reaches: the bytes its calls must move, from their shapes, over the
chip's HBM bandwidth, divided by the device time of its events."""
from bench.harness.costs import sparsify_quantize_ef_bytes
from bench.harness.trace import op_seconds

LAYER = "kernels"
UNIT = "%"
MOVES = "rounds_per_s"


def is_kernel(name: str) -> bool:
    """The kernel's custom calls, named after its jitted wrapper
    (``vmap_jit_sparsify_quantize_ef__.<n>`` under the codec's vmap)."""
    return "sparsify_quantize_ef" in name


def read(trace):
    seconds = op_seconds(trace, is_kernel)
    if seconds == 0:
        return None
    nbytes = sparsify_quantize_ef_bytes(
        trace["kernel_elements_per_round"]) * trace["rounds"]
    return 100.0 * nbytes / trace["peaks"]["hbm_bytes_per_s"] / seconds
