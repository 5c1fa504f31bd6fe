"""Share of its roofline of the fused dual-probe ZO matmul
(``kernels/zo_matmul.zo_dual_matmul``) over the traced window: the least
time its calls need (their FLOPs at the bf16 peak, or their operand and
result bytes at the HBM peak, whichever is larger, counted from each
call's shapes in the compiled programs) over their summed device time."""
from lib import kernels as K
from lib import roofline


def match(call):
    return call["wrapper"] == "zo_dual_matmul"


def work(call):
    # xa (.., M, K), xb (.., M, K), w (.., K, N): two streams
    arr = K.arrays(call)
    x, w = arr[0][1], arr[-1][1]
    return {"flops": 2 * 2 * K.lead(x, 2) * x[-2] * x[-1] * w[-1],
            "bytes": K.io_bytes(call)}


def read(record):
    return roofline.share(record, match, work)
