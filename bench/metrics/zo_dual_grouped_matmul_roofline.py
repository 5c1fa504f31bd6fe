"""Share of its roofline of the grouped dual-probe ZO matmul over the held
experts (``kernels/grouped_matmul.zo_dual_grouped_matmul``) over the
traced window, in %.

Its work is counted from the round's ``moe_rows`` counter (the traced
rounds' (token, held expert) rows, both streams, every layer and client;
padding not counted), never from the worst-case buffers the calls
receive: each row passes the expert's three projections (up, gate,
down), 2 * K * N FLOPs each, K * N from the calls' weight operand.  Bytes:
each call's held-expert weights once, plus every row's input and output
of each projection.  The least time is the larger of the summed FLOPs at
the bf16 peak and the summed bytes at the HBM peak, over the summed
device time of the calls: the larger of two sums is at most the sum of
the larger ones, so the share can only read low."""
import math

from lib import kernels as K
from lib import trace as TR

PROJECTIONS = 3          # a gated expert FFN: up, gate, down


def match(call):
    return call["wrapper"] == "zo_dual_grouped_matmul"


def read(record):
    rows, tr = record.get("moe_rows"), record.get("trace")
    if tr is None or not rows:
        return None
    weights = spent = 0.0
    kn = itemsize = None
    for module, calls in record.get("programs", {}).items():
        mine = {n: c for n, c in calls.items() if match(c)}
        for e in TR.kernel_events(tr, mine, module):
            dt, w = K.arrays(mine[e.name])[-1]          # (E, K, N)
            weights += K.nbytes([(dt, w)])
            kn, itemsize = w[-2:], K.DTYPE_BYTES[dt]
            spent += e.dur
    if spent <= 0:
        return None
    flops = rows * PROJECTIONS * 2 * math.prod(kn)
    moved = weights + rows * PROJECTIONS * sum(kn) * itemsize
    peak = record["peak"]
    least = max(flops / peak["bf16_flops_per_s"],
                moved / peak["hbm_bytes_per_s"])
    return 100.0 * least / spent
